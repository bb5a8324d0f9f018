"""Spans, counters and request stamps of the serving path, and the
benchmark readers that read them.

The profiler runs in this file only (one process runs one profiler)."""
import gc
import sys
import types
from pathlib import Path

import jax
import numpy as np
import pytest

import repro.configs as cfgs
from repro.core.compat import make_mesh
from repro.models import build_model
from repro.runtime import spans as S
from repro.runtime.dist import make_dist
from repro.serve.engine import Request, ServeEngine
from repro.serve.supervisor import ServeSupervisor

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import harness as H  # noqa: E402
from bench.metrics import _program  # noqa: E402
from bench.metrics._trace import WINDOW, Trace  # noqa: E402

MS = 1_000_000


@pytest.fixture(scope="module")
def model():
    cfg = cfgs.smoke_config("qwen2-0.5b")
    api = build_model(cfg)
    return cfg, api, api.init(jax.random.PRNGKey(0))


def _engine(model, **kw):
    cfg, api, params = model
    dist = make_dist(make_mesh((1, 1), ("data", "model")), impl="paxi")
    return ServeEngine(api, params, max_batch=3, max_seq=64, block_size=4,
                       prefill_chunk=4, dist=dist, **kw)


def _requests(cfg):
    rng = np.random.default_rng(3)
    return [Request(i, rng.integers(1, cfg.vocab_size, n).astype(np.int32),
                    max_new_tokens=m, temperature=t, top_k=k)
            for i, (n, m, t, k) in enumerate(
                [(6, 5, 0.8, 4), (9, 4, 0.0, 0), (3, 6, 1.1, 0)])]


def _inside(inner, outer):
    return outer.start <= inner.start and inner.end <= outer.end


# ---------------------------------------------------------------------------
# (a) the spans of a traced engine, read back from the profile
# ---------------------------------------------------------------------------
def test_serving_spans_nest_in_the_profile(model, tmp_path):
    eng = _engine(model)
    reqs = _requests(model[0])
    with jax.profiler.trace(str(tmp_path)):
        eng.run(reqs)
        gc.collect()
    spans = _program.read_spans(tmp_path)
    assert {s.name for s in spans} <= {v for k, v in vars(S).items()
                                       if k.isupper() and isinstance(v, str)}
    named = lambda n: sorted((s for s in spans if s.name == n),
                             key=lambda s: s.start)
    steps, decodes = named(S.SERVE_STEP), named(S.SERVE_DECODE)
    assert len(steps) == eng.stats["steps"]
    assert len(decodes) == eng.stats["decode_steps"] > 1
    assert len(named(S.SERVE_PREFILL)) == eng.stats["prefill_chunks"]
    for d in decodes:
        assert any(_inside(d, s) for s in steps)
        for n in (S.SERVE_DECODE_DISPATCH, S.SERVE_DECODE_WAIT,
                  S.SERVE_DECODE_COPY, S.SERVE_SAMPLE, S.SERVE_SYNC,
                  S.REGION_LOWER, S.REGION_RUN):
            assert sum(_inside(x, d) for x in named(n)) == 1, n
        # the row programs are dispatched before the wait for their tokens
        order = [next(x for x in named(n) if _inside(x, d)) for n in (
            S.SERVE_DECODE_DISPATCH, S.SERVE_SAMPLE, S.SERVE_DECODE_WAIT,
            S.SERVE_DECODE_COPY, S.SERVE_SYNC)]
        assert all(a.end <= b.start for a, b in zip(order, order[1:]))
    # one lowering per decode step; the one compile at the first
    assert len(named(S.REGION_LOWER)) == len(decodes)
    compiles = named(S.REGION_COMPILE)
    assert len(compiles) == 1 and _inside(compiles[0], decodes[0])
    assert named(S.HOST_GC)              # the collection above, at least
    # the counters beside the spans
    assert eng.decode_sync.calls == eng.stats["decode_steps"]
    assert eng.decode_sync.compiles == 1
    assert eng.stats["prefill_positions"] == 4 * eng.stats["prefill_chunks"]
    assert eng.stats["prefill_tokens"] == sum(len(r.prompt) for r in reqs)
    assert eng.stats["decode_rows"] == sum(len(r.out_tokens) - 1
                                           for r in reqs)
    # the programs carry the names of their functions
    assert eng._prefill_chunk_fn.__name__ == "prefill_chunk_paged"
    assert eng._decode_paged.__name__ == "decode_step_paged"


def test_prefill_and_decode_spans_carry_their_arguments(model, tmp_path):
    from bench.metrics import _span_args
    eng = _engine(model)
    reqs = _requests(model[0])
    with jax.profiler.trace(str(tmp_path)):
        eng.run(reqs)
    got = _span_args.read_spans(tmp_path, (S.SERVE_PREFILL, S.SERVE_DECODE))
    prefill = [a for e, a in got if e.name == S.SERVE_PREFILL]
    decode = [a for e, a in got if e.name == S.SERVE_DECODE]
    assert len(prefill) == eng.stats["prefill_chunks"]
    assert all(a["chunk"] == 4 and 0 < a["real"] <= 4 for a in prefill)
    assert sum(a["real"] for a in prefill) == eng.stats["prefill_tokens"]
    assert sorted(a["start"] for a in prefill) == sorted(
        s for r in reqs for s in range(0, len(r.prompt), 4))
    assert len(decode) == eng.stats["decode_steps"]
    assert sum(a["rows"] for a in decode) == eng.stats["decode_rows"]
    # each decoding row attends its prompt and every token before its own
    assert sum(a["context"] for a in decode) == sum(
        len(r.prompt) + k for r in reqs for k in range(1, len(r.out_tokens)))


def test_gc_span_installs_once():
    S.install_gc_span()
    S.install_gc_span()
    assert gc.callbacks.count(S._gc_span) == 1
    gc.collect()                         # no profiler: enter/exit are cheap
    assert S._gc_open[0] is None


# ---------------------------------------------------------------------------
# (b) the readers on synthetic traces and run records
# ---------------------------------------------------------------------------
def _reader(name):
    return H.load_module(H.BENCH / "metrics" / f"{name}.py", "metrics")


OLD = ("queue_wait_p95_ms.serve", "prefill_chunk_device_ms.serve",
       "decode_step_device_ms.serve", "decode_sync_ms.serve",
       "device_idle_share.serve")

# device 0: decode "d(1)" 10-30 and 60-80, prefill "p(2)" 40-50; markers after
OPS = {0: [("%fusion.1 = bf16[8] fusion(...)", 10 * MS, 20 * MS),
           ("%fusion.2 = bf16[8] fusion(...)", 40 * MS, 10 * MS),
           ("%fusion.3 = bf16[8] fusion(...)", 60 * MS, 20 * MS)]}
MODULES = {0: [("d(1)", 10 * MS, 20 * MS), ("p(2)", 40 * MS, 10 * MS),
               ("d(1)", 60 * MS, 20 * MS),
               ("p(2)", 110 * MS, 2 * MS), ("d(1)", 120 * MS, 3 * MS)]}
BENCH_SPANS = [(WINDOW, 0, 100 * MS),
               ("bench.step", 5 * MS, 50 * MS),
               ("bench.sample", 31 * MS, 6 * MS),
               ("bench.sync", 38 * MS, 2 * MS),
               ("bench.mark.prefill", 109 * MS, 4 * MS),
               ("bench.mark.decode", 119 * MS, 5 * MS)]
# two decode steps in the window (and one that ends after it)
PAX_SPANS = [("pax.serve.step", 6 * MS, 48 * MS),
             ("pax.serve.decode", 8 * MS, 33 * MS),
             ("pax.serve.decode.copy", 30 * MS, 1 * MS),
             ("pax.serve.sample", 31 * MS, 6 * MS),
             ("pax.host.gc", 33 * MS, 2 * MS),
             ("pax.serve.sync", 38 * MS, 2 * MS),
             ("pax.abi.region.lower", 38 * MS, 1 * MS),
             ("pax.serve.decode", 58 * MS, 34 * MS),
             ("pax.serve.decode.copy", 80 * MS, 3 * MS),
             ("pax.serve.sample", 83 * MS, 2 * MS),
             ("pax.abi.region.lower", 85 * MS, 3 * MS),
             ("pax.serve.decode", 95 * MS, 10 * MS),
             ("pax.serve.decode.copy", 96 * MS, 8 * MS)]


def _req(t_submit=None, t_admit=None, t_first=None):
    return types.SimpleNamespace(t_submit=t_submit, t_admit=t_admit,
                                 t_first=t_first, done=False)


def _rec(due, admit, req):
    return types.SimpleNamespace(due=due, admit=admit, tokens=[], req=req)


def _run(trace, requests=(), program_spans=()):
    """A run record; ``program_spans`` stand for what
    ``_program.spans`` reads from the run's profile."""
    spans = H.Spans()
    spans.records = [("bench.sync", 0.5, 0.502), ("bench.sync", 0.6, 0.604),
                     ("bench.sync", 1.5, 1.6)]
    extra = {"program_spans": Trace.from_events({}, {}, program_spans).spans}
    return types.SimpleNamespace(trace_data=trace, window=(0.0, 1.0),
                                 records={"requests": list(requests)},
                                 spans=spans, extra=extra)


# due in the window: submitted/admitted/first token, by hand below
RECS = [_rec(0.10, 0.15, _req(0.11, 0.15, 0.65)),  # wait 40 ms, first 500
        _rec(0.20, 0.30, _req(0.21, 0.30, None)),  # wait 90, no token: 700
        _rec(0.30, None, _req(0.31, None, None)),  # never admitted: 690
        _rec(0.40, 0.45, _req(0.41, 0.45, 1.20)),  # wait 40, token late: 550
        _rec(0.50, None, _req()),                  # refused: no stamps
        _rec(-0.5, 0.20, _req(-0.49, 0.20, 0.30))]  # lead-in: not counted


def test_new_readers_on_hand_worked_records():
    run = _run(Trace.from_events(OPS, MODULES, BENCH_SPANS), RECS, PAX_SPANS)
    # copy (1 + 3) ms, sample (6 + 2), lower (1 + 3), over 2 decode steps
    assert _reader("logits_copy_ms.serve").read(run) == pytest.approx(2.0)
    assert _reader("sample_host_ms.serve").read(run) == pytest.approx(4.0)
    assert _reader("sync_lower_ms.serve").read(run) == pytest.approx(2.0)
    assert _reader("admit_wait_p95_ms.serve").read(run) == pytest.approx(
        np.percentile([40, 90, 690, 40], 95))
    assert _reader("admit_to_first_p95_ms.serve").read(run) == pytest.approx(
        np.percentile([500, 700, 550], 95))


@pytest.mark.parametrize("name", ["logits_copy_ms.serve",
                                  "sample_host_ms.serve",
                                  "sync_lower_ms.serve",
                                  "admit_wait_p95_ms.serve",
                                  "admit_to_first_p95_ms.serve"])
def test_new_readers_read_nothing_without_the_program(name):
    """A program with no spans and no stamps (an older one) reads None."""
    bare = [_rec(r.due, r.admit, types.SimpleNamespace(done=False))
            for r in RECS]
    assert _reader(name).read(
        _run(Trace.from_events(OPS, MODULES, BENCH_SPANS), bare)) is None
    assert _reader(name).read(_run(None, [])) is None


def test_old_readers_and_breakdown_ignore_program_spans():
    without = _run(Trace.from_events(OPS, MODULES, BENCH_SPANS), RECS)
    with_pax = _run(Trace.from_events(OPS, MODULES,
                                      BENCH_SPANS + PAX_SPANS), RECS)
    got = {n: _reader(n).read(without) for n in OLD}
    assert got == {n: _reader(n).read(with_pax) for n in OLD}
    assert got["prefill_chunk_device_ms.serve"] == pytest.approx(10.0)
    assert got["decode_step_device_ms.serve"] == pytest.approx(20.0)
    assert got["decode_sync_ms.serve"] == pytest.approx(3.0)
    assert got["device_idle_share.serve"] == pytest.approx(50.0)
    assert (without.trace_data.breakdown()["device_ops"]
            == with_pax.trace_data.breakdown()["device_ops"])


def test_idle_gap_is_named_by_the_innermost_span():
    t = Trace.from_events(OPS, MODULES, BENCH_SPANS + PAX_SPANS)
    # idle 0-10, 30-40, 50-60 and 80-100, named at their midpoints: 35
    # lies in pax.host.gc inside bench.sample, 90 in a decode step only
    assert sorted(t.breakdown()["idle_gaps"]) == sorted([
        ["bench.step", 0.01], ["pax.host.gc", 0.01], ["bench.step", 0.01],
        ["pax.serve.decode", 0.02]])
    assert t.host_span_at(32 * MS) == "bench.sample"
    assert t.host_span_at(86 * MS) == "pax.abi.region.lower"


# ---------------------------------------------------------------------------
# (c) request stamps
# ---------------------------------------------------------------------------
def test_request_stamps_are_ordered(model):
    eng = _engine(model)
    reqs = _requests(model[0])
    eng.run(reqs)
    for r in reqs:
        assert r.t_submit <= r.t_admit <= r.t_first


def test_replayed_request_keeps_its_first_admission(model):
    eng = _engine(model)
    sup = ServeSupervisor(eng)
    reqs = _requests(model[0])
    for r in reqs:
        eng.submit(r)
    for _ in range(4):
        sup.step()
    admitted = {r.rid: r.t_admit for r in reqs}
    firsts = {r.rid: r.t_first for r in reqs}
    assert all(t is not None for t in admitted.values())
    sup._replay_inflight()               # what a recovery does to slots
    assert sup.report.requeued > 0
    sup.drain()
    assert all(r.done and len(r.out_tokens) == r.max_new_tokens for r in reqs)
    assert {r.rid: r.t_admit for r in reqs} == admitted
    assert {r.rid: r.t_first for r in reqs if firsts[r.rid]} == {
        k: v for k, v in firsts.items() if v}
    for r in reqs:
        assert r.t_submit <= r.t_admit <= r.t_first
