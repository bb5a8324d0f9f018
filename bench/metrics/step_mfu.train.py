"""Model step: model FLOP utilization of the whole train step, the
forward and backward operations per token (the cell's family module,
recomputation left out) times the window's tokens per second, over the
chips' bf16 peak (bench/peaks.json)."""
from .. import harness


def read(run):
    steps = run.records.get("steps")
    if not steps or run.rehearse:
        return None
    tps = len(steps) * run.records["tokens_per_step"] / (steps[-1][1] - run.window[0])
    peak = harness.peaks(run.device["kind"])["bf16_flops_per_s"]
    per_token = harness.family(run.cell).train_flops_per_token(
        run.records["dims"], run.records["seq_len"])
    return 100.0 * per_token * tps / (run.device["count"] * peak)
