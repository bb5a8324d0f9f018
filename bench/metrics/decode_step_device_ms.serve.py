"""Model step: mean device time per call of the paged decode program in
the traced window."""

def read(run):
    t = run.trace_data
    name = t and t.program_named_by("bench.mark.decode")
    calls = t.program_calls(name) if name else []
    return sum(e.dur for e in calls) / len(calls) / 1e6 if calls else None
