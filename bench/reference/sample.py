"""Plain reference of the serving engine's stated sampling, and the gap by
which a served token lies below the reference's own choice.

The engine states its sampling as: a greedy request (temperature 0) takes
the largest logit; any other request divides the logits by its
temperature, keeps the ``top_k`` largest, and takes the Gumbel-max draw
under the key ``fold_in(fold_in(PRNGKey(engine seed), request id), i)``
for its ``i``-th token, the noise drawn in the type the logits are served
in.  Given the same keys, the reference makes the same noise and so can
say which token the float32 model would have drawn at each position.

:func:`gaps` reads, for each served token, in logit units:

* ``kth - logits[token]``: how far the token lies below the reference's
  ``k``-th largest logit, so a token from outside the top ``k`` shows
  even where the noise favours it;
* for each token ``j`` of the reference's top ``k`` whose score
  ``logits / T + noise`` beats the served token's, the lesser of
  ``T * (score[j] - score[token])`` and ``logits[j] - next``, where
  ``next`` is the ``(k+1)``-th largest logit; the largest of these.  A
  token at the top-k boundary falls out of the program's top ``k`` when
  rounding lowers its logit by ``logits[j] - next``, and then its noise
  does not count against the program; a token well inside the top ``k``
  counts in full.

For a greedy request it is ``max(logits) - logits[token]``.  The gap
without the allowance at the boundary is returned beside it (``strict``):
with bf16 logits a boundary token wins the reference's draw now and
then, and that reading then swings with the draw.  A sound program lies
within its rounding of the reference; a sampler that ignores ``top_k``,
draws under another key or alters the token does not.  It imports
nothing of the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("vocab", "dtype"))
def noise(seed, rid, steps, *, vocab: int, dtype: str):
    """Gumbel noise ``(len(steps), vocab)`` of request ``rid``'s tokens."""
    base = jax.random.fold_in(jax.random.PRNGKey(seed), rid)
    keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(steps)
    return jax.vmap(lambda k: jax.random.gumbel(k, (vocab,), dtype))(keys)


@functools.partial(jax.jit, static_argnames=("temperature", "top_k"))
def first(logits, g, *, temperature: float, top_k: int):
    """The token each row of ``logits`` would serve under noise ``g``."""
    if temperature <= 0.0:
        return jnp.argmax(logits, -1)
    s = logits / temperature + g
    if top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
        s = jnp.where(logits < kth, -jnp.inf, s)
    return jnp.argmax(s, -1)


@functools.partial(jax.jit, static_argnames=("temperature", "top_k"))
def gaps(logits, g, tokens, *, temperature: float, top_k: int):
    """Per served token, ``(gap, strict)`` in logit units: ``gap`` as the
    module says, ``strict`` without the allowance at the top-k boundary."""
    rows = jnp.arange(tokens.shape[0])
    own = logits[rows, tokens]
    if temperature <= 0.0:
        gap = logits.max(-1) - own
        return gap, gap
    s = logits / temperature + g
    beat = temperature * (s - s[rows, tokens][:, None])
    if top_k <= 0:
        gap = beat.max(-1)
        return gap, gap
    vals = jax.lax.top_k(logits, top_k + 1)[0]
    kth, nxt = vals[:, top_k - 1:top_k], vals[:, top_k:]
    inside = logits >= kth
    below = kth[:, 0] - own
    strict = jnp.where(inside, beat, -jnp.inf).max(-1)
    clear = jnp.where(inside, jnp.minimum(beat, logits - nxt), -jnp.inf)
    return (jnp.maximum(clear.max(-1), below),
            jnp.maximum(strict, below))


def request_gaps(seed: int, rid: int, logits: np.ndarray, tokens: np.ndarray,
                 *, temperature: float, top_k: int, noise_dtype: str,
                 rows_pad: int, control_logits: np.ndarray | None = None
                 ) -> tuple[np.ndarray, np.ndarray]:
    """``(gap, strict)`` of one request's served ``tokens`` in the float32
    ``logits`` at their positions; with ``control_logits``, of the tokens
    those logits put first instead (the control).  Rows are padded to
    ``rows_pad``, so every request runs the same compiled programs."""
    n = len(tokens)

    def pad(x):
        x = np.asarray(x, np.float32)
        return jnp.asarray(np.pad(x, ((0, rows_pad - n), (0, 0))))

    steps = jnp.arange(rows_pad, dtype=jnp.uint32)
    g = (noise(seed, rid, steps, vocab=logits.shape[-1], dtype=noise_dtype)
         .astype(jnp.float32) if temperature > 0.0 else
         jnp.zeros((rows_pad, 1), jnp.float32))
    logits = pad(logits)
    tokens = jnp.asarray(np.pad(np.asarray(tokens, np.int32), (0, rows_pad - n)))
    if control_logits is not None:
        tokens = first(pad(control_logits), g, temperature=temperature,
                       top_k=top_k)
    gap, strict = gaps(logits, g, tokens, temperature=temperature, top_k=top_k)
    return np.asarray(gap)[:n], np.asarray(strict)[:n]
