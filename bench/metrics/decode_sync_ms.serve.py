"""Decode sync through the ABI: mean host time of ``DecodeSync.step`` (the
``decode-tp`` plan group's start/wait) per decode step in the window."""


def read(run):
    d = run.spans.durations("bench.sync", *run.window)
    return sum(d) / len(d) * 1e3 if d else None
