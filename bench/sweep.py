"""Offered-load sweep of an open-loop cell, to find its knee: the highest
rate at which the queue does not grow over the window. The cell's traffic
file then fixes its rate at about four fifths of the knee. Run once, when a
cell is defined; the benchmark's own runs never search for a rate.

    python3 bench/sweep.py --workload <cell> --rates 40,60,80 --seconds 30

For each rate it prints the latency median and 95th percentile, and the
mean latency of the first and last quarter of the requests by due time: a
queue that grows shows as a last quarter well above the first, and as a
last batch that ends long after the window.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if __name__ == "__main__":
    if sys.path and os.path.abspath(sys.path[0] or ".") == BENCH:
        sys.path.pop(0)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    import numpy as np
    from bench import harness
    try:
        cell = harness.load_cell(args.workload)
        harness.enable_cache()
        harness.require_tpu(cell.chips)
    except (harness.BenchError, ImportError, OSError, KeyError) as e:
        harness.log(f"FAILED: {e}")
        return 1
    if cell.mix["loop"] != "open":
        harness.log("FAILED: only an open-loop cell has a knee to sweep")
        return 1
    counter = harness.CompileCounter()
    server = harness.Server(cell)
    server.warmup()
    base = dict(cell.mix)
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.mix = dict(base, rate_rps=rate)
        w = harness.run_window(server, args.seed, args.seconds, counter)
        lat = sorted((w.due[rid], b.end - w.due[rid])
                     for b in w.batches for rid, _, _ in b.requests)
        q = max(1, len(lat) // 4)
        first = float(np.mean([x for _, x in lat[:q]]))
        last = float(np.mean([x for _, x in lat[-q:]]))
        walls = [b.wall_s for b in w.batches]
        print(json.dumps({
            "rate_rps": rate, "requests": len(lat),
            "batches": len(w.batches),
            "batch_wall_s_median": float(np.median(walls)),
            "requests_per_batch": len(lat) / max(1, len(w.batches)),
            "p50_ms": float(np.percentile([x for _, x in lat], 50)) * 1e3,
            "p95_ms": float(np.percentile([x for _, x in lat], 95)) * 1e3,
            "first_quarter_ms": first * 1e3, "last_quarter_ms": last * 1e3,
            "overrun_s": w.wall_s - args.seconds,
            "compiles": w.compiles}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
