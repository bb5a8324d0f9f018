"""The program's own host spans in a traced run: the shared code of the
readers of program spans.

The program names its spans ``pax.<...>`` (the catalog is
``src/repro/runtime/spans.py``).  :mod:`._trace` keeps only the
harness's ``bench.`` spans, so these are read from the same profile on
their own, once per run.  A program without them reads an empty list.
"""
from __future__ import annotations

from pathlib import Path

from . import _trace

PREFIX = "pax."
#: one per decode step: the denominator of the per-step readers
DECODE = "pax.serve.decode"


def read_spans(log_dir) -> list[_trace.Event]:
    """Every host span named ``pax.<...>`` in the newest profile under
    ``log_dir``, on the profile's clock (ns)."""
    files = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        return []
    import jax
    pd = jax.profiler.ProfileData.from_file(str(files[-1]))
    return [_trace.Event(e.name, e.start_ns, e.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIX)]


def spans(run) -> list[_trace.Event]:
    """The run's program spans, read from its profile on first use."""
    if "program_spans" not in run.extra:
        from ..harness import TRACE_DIR
        run.extra["program_spans"] = read_spans(TRACE_DIR)
    return run.extra["program_spans"]


def ms_per_decode_step(run, name: str) -> float | None:
    """Host ms of the spans ``name`` inside the traced window, per
    ``pax.serve.decode`` span there; None with no decode span."""
    t = run.trace_data
    if t is None:
        return None
    t0, t1 = t.window()
    inside = [s for s in spans(run) if s.start >= t0 and s.end <= t1]
    steps = sum(s.name == DECODE for s in inside)
    if not steps:
        return None
    return sum(s.dur for s in inside if s.name == name) / steps / 1e6
