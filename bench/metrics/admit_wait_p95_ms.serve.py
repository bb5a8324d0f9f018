"""Scheduler: 95th percentile of the engine's own stamps from submission
to first admission (``Request.t_submit`` to ``t_admit``), over the
requests due in the window; one not admitted by the close counts its wait
to the close.  A program without the stamps reads nothing."""
from . import _serve


def read(run):
    end = run.window[1]
    waits = []
    for r in _serve.due_in_window(run):
        t_submit = getattr(r.req, "t_submit", None)
        if t_submit is None:
            continue
        t_admit = r.req.t_admit
        waits.append((t_admit if t_admit is not None and t_admit <= end
                      else end) - t_submit)
    return _serve.p95_ms(waits)
