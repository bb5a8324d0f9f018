"""Serve engine sampling: per-request sampling params (the batch once ran
entirely under requests[0]'s temperature/top_k), the compiled row program
against the module's ``sample``, and the two seams a replaced sampler
goes through (``ServeEngine._sample_one`` and ``ServeEngine._req_key``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as cfgs
from repro.models import build_model
from repro.serve.engine import Request, ServeEngine, sample


def _engine(max_batch=2):
    cfg = cfgs.smoke_config("qwen2-0.5b")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    return ServeEngine(api, params, max_batch=max_batch, max_seq=64)


def test_mixed_batch_honors_each_requests_params():
    prompt = np.arange(1, 9, dtype=np.int32)
    ref = _engine().generate(prompt, max_new_tokens=8)  # solo greedy

    eng = _engine()
    hot = Request(0, prompt, max_new_tokens=8, temperature=5.0)
    greedy = Request(1, prompt, max_new_tokens=8, temperature=0.0)
    eng.run([hot, greedy])
    # the greedy row must be untouched by its neighbor's temperature —
    # with the old batch-wide requests[0] params it would have sampled hot
    assert greedy.out_tokens == list(ref)
    assert len(hot.out_tokens) == 8


def test_per_request_max_new_tokens():
    prompt = np.arange(1, 9, dtype=np.int32)
    eng = _engine()
    # max_new_tokens=1 is the edge: the cap must apply to the very first
    # (prefill-sampled) token too, not only to decode-loop tokens
    one = Request(0, prompt, max_new_tokens=1)
    short = Request(1, prompt, max_new_tokens=3)
    eng.run([one, short])
    assert len(one.out_tokens) == 1
    assert len(short.out_tokens) == 3

    eng2 = _engine()
    long = Request(0, prompt, max_new_tokens=8)
    eng2.run([long])
    assert len(long.out_tokens) == 8


def test_homogeneous_batch_single_group():
    prompt = np.arange(1, 9, dtype=np.int32)
    eng = _engine()
    reqs = [Request(i, prompt, max_new_tokens=4, temperature=0.0) for i in range(2)]
    eng.run(reqs)
    assert reqs[0].out_tokens == reqs[1].out_tokens  # same prompt, greedy


# ---------------------------------------------------------------------------
# the row program against the module's sample
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def qwen():
    cfg = cfgs.smoke_config("qwen2-0.5b")
    api = build_model(cfg)
    return api, api.init(jax.random.PRNGKey(0))


def _req(rid, temperature, top_k, step=0):
    return Request(rid, np.ones(1, np.int32), temperature=temperature,
                   top_k=top_k, out_tokens=[0] * step)


@pytest.fixture(scope="module")
def row_engine(qwen):
    return ServeEngine(*qwen, max_batch=2, max_seq=64, seed=2**31 - 7)


@pytest.fixture(scope="module", params=["bfloat16", "float32"])
def rows(request):
    rng = np.random.default_rng(11)
    return jnp.asarray(rng.normal(0, 2.0, (6, 4096)), request.param)


@pytest.mark.parametrize("top_k", [0, 1, 5, 50])
@pytest.mark.parametrize("temperature", [0.3, 0.7, 1.0, 5.0])
def test_row_program_draws_what_sample_draws(row_engine, rows, temperature,
                                             top_k):
    eng = row_engine
    for i, row in enumerate(rows):
        req = _req(1000 + i, temperature, top_k, step=3 * i)
        want = sample(row, eng._req_key(req.rid, 3 * i), temperature, top_k)
        got = eng._sample_one(row, req)
        assert got.dtype == jnp.int32 and got.shape == ()
        assert int(got) == int(want)


def test_row_program_takes_ids_past_int32(row_engine, rows):
    """Request ids and steps reach fold_in as uint32, as in the eager
    sample."""
    req = _req(2**32 - 3, 0.7, 50, step=2)
    want = sample(rows[0], row_engine._req_key(req.rid, 2), 0.7, 50)
    assert int(row_engine._sample_one(rows[0], req)) == int(want)


def test_greedy_row_is_the_host_argmax(row_engine, rows):
    for i, row in enumerate(rows):
        assert int(row_engine._sample_one(row, _req(i, 0.0, 0))) == \
            int(np.argmax(np.asarray(row)))


def _old_sampler(eng):
    """The sampler before the row program: a host copy of each row and the
    module's eager sample."""
    def sample_one(row_logits, req):
        row = np.asarray(row_logits)
        if req.temperature <= 0.0:
            return int(np.argmax(row))
        key = eng._req_key(req.rid, len(req.out_tokens))
        return int(sample(jnp.asarray(row), key, float(req.temperature),
                          int(req.top_k)))
    return sample_one


def _mixed(vocab, n=5):
    rng = np.random.default_rng(5)
    params = [(0.7, 50), (0.0, 0), (1.3, 5), (0.9, 0), (0.3, 1)]
    return [Request(i, rng.integers(1, vocab, 3 + 2 * i).astype(np.int32),
                    max_new_tokens=6 + i, temperature=t, top_k=k)
            for i, (t, k) in enumerate(params[:n])]


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "rwkv6-7b"])
def test_engine_matches_the_host_copy_loop(arch):
    """Paged (continuous batching) and static paths alike."""
    cfg = cfgs.smoke_config(arch)
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    kw = dict(max_batch=3, max_seq=64, seed=2**31 + 9)
    ref = ServeEngine(api, params, **kw)
    ref._sample_one = _old_sampler(ref)
    eng = ServeEngine(api, params, **kw)
    want, got = _mixed(cfg.vocab_size), _mixed(cfg.vocab_size)
    ref.run(want)
    eng.run(got)
    for w, g in zip(want, got):
        assert len(g.out_tokens) == g.max_new_tokens
        assert g.out_tokens == w.out_tokens, g.rid


def test_counters(qwen):
    eng = ServeEngine(*qwen, max_batch=3, max_seq=64)
    reqs = _mixed(512)
    eng.run(reqs)
    sampled = sum(len(r.out_tokens) for r in reqs if r.temperature > 0)
    assert eng.stats["sampled_rows"] == sampled
    # one trace per (width, dtype, top_k) met: 50, 5, 0, 1
    assert eng.sample_compiles == 4
    eng.run([Request(9, np.arange(1, 6, dtype=np.int32), max_new_tokens=4,
                     temperature=2.5, top_k=50)])   # a new temperature
    assert eng.sample_compiles == 4
    assert eng.stats["sampled_rows"] == sampled + 4


# ---------------------------------------------------------------------------
# the seams: a replaced key or sampler reaches the decode loop's tokens
# ---------------------------------------------------------------------------
def _served(qwen):
    eng = ServeEngine(*qwen, max_batch=3, max_seq=64, seed=77)
    reqs = [Request(i, np.arange(1 + i, 7 + i, dtype=np.int32),
                    max_new_tokens=6, temperature=1.5, top_k=0)
            for i in range(3)]
    eng.run(reqs)
    return [r.out_tokens for r in reqs]


def _after_first(good):
    """``good`` shifted by one from the second token on, so that only the
    decode loop's tokens can tell."""
    def seam(self, rid, step):
        return good(self, rid, jnp.where(step >= 1, step + 1, step))
    return seam


def test_replaced_key_reaches_the_decode_loop(qwen, monkeypatch):
    base = _served(qwen)
    monkeypatch.setattr(ServeEngine, "_req_key",
                        _after_first(ServeEngine._req_key))
    got = _served(qwen)
    assert [t[0] for t in got] == [t[0] for t in base]
    assert [t[1:] for t in got] != [t[1:] for t in base]


def test_replaced_sampler_reaches_the_decode_loop(qwen, monkeypatch):
    base = _served(qwen)
    good = ServeEngine._sample_one
    calls = []

    def altered(self, row_logits, req):
        calls.append(len(req.out_tokens))
        tok = good(self, row_logits, req)
        return tok if not req.out_tokens else (tok + 1) % self.cfg.vocab_size
    monkeypatch.setattr(ServeEngine, "_sample_one", altered)
    got = _served(qwen)
    assert sorted(calls) == sorted(list(range(6)) * 3)   # every token
    assert [t[0] for t in got] == [t[0] for t in base]
    assert all(g[1] == (b[1] + 1) % 512 for g, b in zip(got, base))


def test_row_program_does_not_depend_on_the_seed(qwen):
    """The seed reaches the row program as an argument, so engines with
    other seeds share one program (and one persistent-cache entry)."""
    row = jnp.zeros((512,), jnp.float32)
    args = np.uint32(3), np.uint32(4), np.float32(0.7)
    texts = {ServeEngine(*qwen, max_batch=2, max_seq=64, seed=s)._sample_row
             .lower(row, jax.random.PRNGKey(s), *args, top_k=5).as_text()
             for s in (1, 2**31 + 5)}
    assert len(texts) == 1
