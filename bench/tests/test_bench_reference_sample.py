"""The reference's draw follows the engine's stated sampling: on the same
bf16 logits it lies within rounding of the engine's tokens, and a draw
under another key or without top-k lies far from it.  A top-k of 5 on
logits spread by 1.5, where ignoring it moves most draws well below the
5th logit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import sample as ref

SEED, RID, N, VOCAB, T, K = 2**31 - 5, 17, 24, 32768, 0.7, 5


def served(logits, *, key_shift=0, top_k=K):
    from repro.serve.engine import sample
    base = jax.random.fold_in(jax.random.PRNGKey(SEED), RID)
    return np.array([int(sample(logits[i], jax.random.fold_in(base, i + key_shift),
                                T, top_k)) for i in range(N)])


@pytest.fixture(scope="module")
def logits():
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.normal(0, 1.5, (N, VOCAB)), jnp.bfloat16)


def gap(logits, tokens):
    g, strict = ref.request_gaps(SEED, RID, np.asarray(logits, np.float32),
                                 tokens, temperature=T, top_k=K,
                                 noise_dtype="bfloat16", rows_pad=N + 8)
    assert np.all(g <= strict + 1e-6)
    return float(g.max())


def test_the_engines_own_draw_is_within_rounding(logits):
    assert gap(logits, served(logits)) < 0.1


@pytest.mark.parametrize("fault", [{"key_shift": 1}, {"top_k": 0}])
def test_a_wrong_draw_is_far(logits, fault):
    assert gap(logits, served(logits, **fault)) > 0.5
