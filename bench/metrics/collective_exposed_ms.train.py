"""ZeRO-1 sync through the ABI: device 0's time per train step in
collective operations while no other operation runs, in the traced
window."""

STEP = "jit_step_fn"


def read(run):
    t = run.trace_data
    if t is None or not t.devices:
        return None
    calls = [e for e in t.program_calls_by_prefix(STEP, 0)]
    if not calls:
        return None
    return t.exposed_collective_ns(0) / len(calls) / 1e6
