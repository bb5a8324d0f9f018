#!/usr/bin/env python3
"""Readings for a cell's limits: the program's compared numbers and the
control's, over many seeds, in one process.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed it runs the cell as ``bench/run.py`` does (set-up, window at
the cell's own load, check) and also computes the control: the float32
reference put in the program's place at the precision one step below the
configuration's (float8 e4m3 matrix products), read at the same prompts
and tokens.  One JSON line per seed: ``program`` and ``control``, each
number the cell compares.
The limit sits between the program's largest reading and the control's
smallest (PERF.md gives both and the limit).  With ``--plant <fault>`` it
reads the program with that fault of ``bench/faults.py`` planted instead
(no control).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO / "src"), str(REPO)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--plant", help="a fault of bench/faults.py to plant first")
    args = ap.parse_args(argv)
    # libtpu would log to a fixed path under /tmp; keep nothing outside
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from bench import harness as H
    if args.plant:
        from bench.faults import FAULTS
        FAULTS[args.plant]()
    bench = H.load_bench()
    cell = H.load_cell(args.workload, bench)
    H.require_devices(cell.chips, args.rehearse)
    H.use_compile_cache()
    driver = H.load_module(H.BENCH / "drivers" / f"{cell.kind}.py", "drivers")
    for seed in (int(s) for s in args.seeds.split(",")):
        run = H.Run(cell, seed, args.seconds, False, args.rehearse,
                    time.perf_counter())
        run.extra["control"] = not args.plant
        run.extra["calibrating"] = True
        driver.run(run)
        print(json.dumps({"phase": "calibrate", "seed": seed,
                          "program": run.extra.get("program_gaps"),
                          "control": run.extra.get("control_gaps"),
                          "strict": run.extra.get("strict_gaps"),
                          "attempted": run.attempted, "failed": run.failed,
                          "setup_s": run.setup_s}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
