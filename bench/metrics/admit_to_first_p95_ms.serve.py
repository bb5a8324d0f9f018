"""Scheduler: 95th percentile of the engine's own stamps from first
admission to first token (``Request.t_admit`` to ``t_first``), over the
requests due in the window and admitted by its close; one with no token
by the close counts its wait to the close.  A program without the stamps
reads nothing."""
from . import _serve


def read(run):
    end = run.window[1]
    waits = []
    for r in _serve.due_in_window(run):
        t_admit = getattr(r.req, "t_admit", None)
        if t_admit is None or t_admit > end:
            continue
        t_first = r.req.t_first
        waits.append((t_first if t_first is not None and t_first <= end
                      else end) - t_admit)
    return _serve.p95_ms(waits)
