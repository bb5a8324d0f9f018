"""Program spans with their arguments, and the share of a roofline a
model program reaches: the shared code of the roofline readers.

The engine sets arguments on two spans while a profiler records
(``src/repro/runtime/spans.py``): ``pax.serve.prefill`` carries the
chunk's ``start``, ``real`` and ``chunk``, ``pax.serve.decode`` its
``rows`` and ``context``.  The profile keeps them as the event's stats.
A share counts only the work the spans say was needed (operations or
bytes, from the configuration's shapes), never what the program happens
to move, so it cannot pass 100 %.  A program whose spans carry no
arguments reads nothing.
"""
from __future__ import annotations

from pathlib import Path

from . import _trace
from .. import flops

PREFILL = "pax.serve.prefill"
DECODE = "pax.serve.decode"
#: bytes of one weight or K/V element, served in bfloat16
BF16 = 2


def read_spans(log_dir, names) -> list[tuple[_trace.Event, dict]]:
    """Host spans named in ``names`` in the newest profile under
    ``log_dir``, each with its arguments, on the profile's clock (ns)."""
    files = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        return []
    import jax
    pd = jax.profiler.ProfileData.from_file(str(files[-1]))
    return [(_trace.Event(e.name, e.start_ns, e.duration_ns), dict(e.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events if e.name in names]


def spans(run, name: str, keys) -> list[dict]:
    """The arguments of the spans ``name`` inside the traced window that
    carry every one of ``keys`` (read from the run's profile on first
    use)."""
    t = run.trace_data
    if t is None:
        return []
    if "program_span_args" not in run.extra:
        from ..harness import TRACE_DIR
        run.extra["program_span_args"] = read_spans(TRACE_DIR,
                                                    (PREFILL, DECODE))
    t0, t1 = t.window()
    return [a for e, a in run.extra["program_span_args"]
            if e.name == name and t0 <= e.start and e.end <= t1
            and all(k in a for k in keys)]


def share(run, span: str, keys, mark: str, work, peak: str) -> float | None:
    """Percent of ``peak`` (a ``bench/peaks.json`` key) that the program
    run inside the harness span ``mark`` reaches: the mean ``work(dims,
    args)`` of the spans ``span`` in the traced window over the mean
    device time of that program's calls there.  None without such spans
    or calls, or when rehearsing (the sizes are then not the file's)."""
    from .. import harness
    t = run.trace_data
    if t is None or run.rehearse:
        return None
    args = spans(run, span, keys)
    name = t.program_named_by(mark)
    calls = t.program_calls(name) if name else []
    if not args or not calls:
        return None
    dims = harness.family(run.cell).file_dims(run.cell.config)
    need = sum(work(dims, a) for a in args) / len(args)
    seconds = sum(e.dur for e in calls) / len(calls) / 1e9
    return 100.0 * need / (seconds * harness.peaks(run.device["kind"])[peak])


def prefill_flops(d, a: dict) -> float:
    """Operations a prefill chunk needs: the layers' matrix products at
    each real position, causal attention at each position's own context
    (position ``p`` attends ``p + 1`` keys), and the head at one position
    (the row the first token is drawn from)."""
    start, real = int(a["start"]), int(a["real"])
    head = d.d_model * d.vocab
    keys = real * start + real * (real + 1) // 2
    attn = 2 * 2 * d.layers * d.heads * d.head_dim
    return 2.0 * (flops.matmul_params(d) - head) * real + attn * keys \
        + 2.0 * head


def decode_bytes(d, a: dict) -> float:
    """Bytes a decode step must read: every weight of a matrix product
    once, and the K and V of each decoding row at its context."""
    per_position = 2 * d.layers * d.kv_heads * d.head_dim * BF16
    return float(BF16 * flops.matmul_params(d)
                 + per_position * int(a["context"]))
