#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell and everything it names are found by name under ``bench/``
(see ``bench/harness.py``).  Set-up (weights from the seed, engine or
trainer, warm-up of the cell's own shapes) comes first and is reported as
``setup_s``; then the window measures for ``--seconds``; then the outputs
are checked against the float32 reference.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` a ``breakdown``, and
last ``checks``, each compared number beside its limit.  The same numbers
end standard error.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 2.  ``--rehearse`` runs the cell at smoke widths on
whatever JAX finds (the CPU here); its metrics carry a ``cpu.`` prefix,
never a device metric's name.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="smoke widths on any platform (CPU rehearsal)")
    args = ap.parse_args(argv)
    # libtpu would log to a fixed path under /tmp; keep nothing outside
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from bench import harness as H
    try:
        bench = H.load_bench()
        cell = H.load_cell(args.workload, bench)
        H.require_devices(cell.chips, args.rehearse)
        import repro  # noqa: F401  (the system under test must be here)
    except (H.BenchError, ImportError, OSError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    H.use_compile_cache()
    run = H.Run(cell, args.seed, args.seconds, bool(args.trace),
                args.rehearse, T_START)
    driver = H.load_module(H.BENCH / "drivers" / f"{cell.kind}.py", "drivers")
    driver.run(run)
    metrics = H.reduce_metrics(run, bench)
    H.print_checks(run)
    print(json.dumps(H.result_line(run, metrics)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
