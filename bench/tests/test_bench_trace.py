"""The trace reducer on a small synthetic event list: busy union, idle
share, exposed collective time, per-program device time."""
import pytest

from bench.metrics._trace import Trace, WINDOW

MS = 1_000_000

# device 0: two programs ("p(1)" decode-like, "p(2)" prefill-like) and ops
OPS = {0: [
    ("%fusion.1 = bf16[8] fusion(...)", 10 * MS, 20 * MS),           # 10-30
    ("%fusion.2 = bf16[8] fusion(...)", 25 * MS, 10 * MS),           # 25-35
    ("%all-reduce.3 = f32[4] all-reduce(...)", 40 * MS, 20 * MS),    # 40-60
    ("%fusion.4 = bf16[8] fusion(...)", 50 * MS, 5 * MS),            # 50-55
    ("%reduce-scatter.5 = f32[4] reduce-scatter(...)", 80 * MS, 10 * MS),
    ("%fusion.6 = bf16[8] fusion(...)", 150 * MS, 10 * MS),          # outside
]}
MODULES = {0: [
    ("p(1)", 10 * MS, 25 * MS),
    ("p(2)", 40 * MS, 20 * MS),
    ("p(1)", 80 * MS, 10 * MS),
    ("p(2)", 105 * MS, 2 * MS),       # the marker call, after the window
]}
SPANS = [
    (WINDOW, 0, 100 * MS),
    ("bench.decode", 5 * MS, 36 * MS),
    ("bench.sync", 60 * MS, 15 * MS),
    ("bench.mark.prefill", 104 * MS, 4 * MS),
]


@pytest.fixture
def trace():
    return Trace.from_events(OPS, MODULES, SPANS)


def test_busy_union_and_idle_share(trace):
    # 10-35 (25) + 40-60 (20) + 80-90 (10) inside 0-100
    assert trace.busy_ns(0) == 55 * MS
    assert trace.window_s() == pytest.approx(0.1)
    assert trace.busy_s() == pytest.approx(0.055)


def test_exposed_collective_time(trace):
    # all-reduce 40-60 minus fusion 50-55 = 15; reduce-scatter 80-90 = 10
    assert trace.exposed_collective_ns(0) == 25 * MS


def test_program_device_time(trace):
    name = trace.program_named_by("bench.mark.prefill")
    assert name == "p(2)"
    assert [e.dur for e in trace.program_calls(name)] == [20 * MS]
    assert [e.dur for e in trace.program_calls("p(1)")] == [25 * MS, 10 * MS]


def test_breakdown_names_gaps_by_host_span(trace):
    b = trace.breakdown()
    # two programs only: the operations that took most time are listed
    assert b["device_ops"][0][1] == pytest.approx(0.02)
    assert "%reduce-scatter.5" in [n for n, _ in b["device_ops"]]
    three = Trace.from_events(OPS, {0: MODULES[0] + [("p(3)", 60 * MS, 1 * MS)]}, SPANS)
    assert three.breakdown()["device_ops"][0] == ["p(1)", pytest.approx(0.035)]
    gaps = dict((round(s, 3), n) for n, s in b["idle_gaps"])
    assert gaps[0.01] in ("host:outside-spans", "bench.decode")
    assert gaps[0.02] == "bench.sync"      # 60-80, midpoint 70 in sync
    assert len(b["idle_gaps"]) <= 10 and len(b["device_ops"]) <= 10
