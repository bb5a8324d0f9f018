"""Device: share of the traced window in which no operation ran on
device 0."""


def read(run):
    t = run.trace_data
    if t is None or not t.devices:
        return None
    return 100.0 * (1.0 - t.busy_ns(0) / 1e9 / t.window_s())
