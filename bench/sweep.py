#!/usr/bin/env python3
"""Find a serving cell's knee: the highest offered rate its engine sustains.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 0.5,1,1.5

One process: weights once, then for each rate a fresh engine and one
open-loop window of the cell's traffic after the cell's lead-in.  Prints one
JSON line per rate (due, finished, queue at the close, tails, tokens/s).
A rate is sustained when the queue at the close stays near zero and the
time to first token does not grow with the window.  The cell file then
fixes ``rate_per_s`` at about four fifths of the knee.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    # libtpu would log to a fixed path under /tmp; keep nothing outside
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import numpy as np

    from bench import harness as H
    from bench.drivers import serve
    from bench.metrics import _serve
    bench = H.load_bench()
    cell = H.load_cell(args.workload, bench)
    H.require_devices(cell.chips, args.rehearse)
    H.use_compile_cache()
    run = H.Run(cell, args.seed, args.seconds, False, args.rehearse, T_START)
    sv = serve.Served(run)
    print(json.dumps({"phase": "setup", "setup_s": time.perf_counter() - T_START}),
          flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        r = H.Run(cell, args.seed + i, args.seconds, False, args.rehearse,
                  T_START, spans=run.spans)
        if i:
            sv.new_engine(run.spans)
        recs = serve._window(r, sv.eng, sv.Request,
                             sv.offered(r.seed, r.seconds, rate), sv.lead_in_s)
        r.records["requests"] = recs
        ttft = _serve.ttfts(r)
        print(json.dumps({
            **serve.window_facts(r, recs, rate),
            "ttft_p50_ms": float(np.percentile(ttft, 50)) * 1e3 if ttft else None,
            "ttft_p95_ms": _serve.p95_ms(ttft),
            "itl_p95_ms": _serve.p95_ms(_serve.token_gaps(r)),
            "queue_wait_p95_ms": _serve.p95_ms(_serve.queue_waits(r)),
            "tokens_per_s": _serve.tokens_in_window(r) / r.seconds}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
