"""One rehearsal of the training cell on four CPU devices, optionally with
a fault planted under the timed path; prints the run's checks as JSON.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python bench/tests/train_case.py [--fault NAME] [--control]

Run in a process of its own by ``test_bench_train_*.py`` (the device count
is fixed when JAX starts).
"""
import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO / "src"), str(REPO)]

CELL = "qwen2-0.5b.train.zero1-dp4"


from bench.faults import FAULTS  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", choices=sorted(FAULTS))
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--seed", type=int, default=2**31 + 9)
    args = ap.parse_args()
    if args.fault:
        FAULTS[args.fault]()
    from bench import harness as H
    bench = H.load_bench()
    if CELL not in [w["name"] for w in bench["workloads"]]:
        # the cell's files exist before BENCHMARK.json lists it
        spec = H.load_json(H.BENCH / "workloads" / f"{CELL}.json")
        bench["workloads"].append({k: spec[k] for k in
                                   ("config", "traffic", "chips")} | {"name": CELL})
    cell = H.load_cell(CELL, bench)
    run = H.Run(cell, args.seed, 2.0, False, True, time.perf_counter())
    run.extra["control"] = args.control
    H.load_module(H.BENCH / "drivers" / "train.py", "drivers").run(run)
    print(json.dumps({"correct": run.correct,
                      "checks": {k: list(v) for k, v in run.checks.items()},
                      "control": run.extra.get("control_gaps"),
                      "attempted": run.attempted, "failed": run.failed}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
