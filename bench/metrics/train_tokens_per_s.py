"""Tokens trained in the window's whole steps over the time of those
steps, the global batch over all chips."""


def read(run):
    steps = run.records.get("steps")
    if not steps:
        return None
    return len(steps) * run.records["tokens_per_step"] / (steps[-1][1] - run.window[0])
