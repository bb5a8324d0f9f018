"""Scheduler: 95th percentile of due time to slot admission (read from
``scheduler.slots`` after each engine step), over the requests due in the
window; one not admitted by the close counts its wait to the close."""
from . import _serve


def read(run):
    return _serve.p95_ms(_serve.queue_waits(run))
