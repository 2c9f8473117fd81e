"""Readings that set the limit of the correctness check.

The program's reading is the widest gap between a served answer and the
plain reference, over every request a window served. The control puts the
reference itself in the program's place, computed in a lower precision
(every product and sum rounded to it), and reads the same gap: a limit that
the control passes would not catch a change to that precision.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 45

sets the cell up once and, for each seed, runs one window at the cell's own
load and prints the program's reading and the control's in each precision
of ``RUNGS``. It runs on a TPU only, as bench/run.py does.
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

RUNGS = ("float32", "bfloat16", "float8_e4m3fn")


def control_reading(cell, window, slots: int, rung: str) -> float:
    """The check's number with the reference, rounded to ``rung``, in
    the program's place on the same requests."""
    from bench import harness, reference
    return harness.check_window(cell, window, slots,
                                dtype=reference.precision(rung))[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import harness
    try:
        cell = harness.load_cell(args.workload)
        harness.enable_cache()
        harness.require_tpu(cell.chips)
    except (harness.BenchError, ImportError, OSError, KeyError) as e:
        harness.log(f"FAILED: {e}")
        return 1
    counter = harness.CompileCounter()
    server = harness.Server(cell)
    server.warmup()
    harness.log(f"set-up {time.perf_counter() - T_START:.3f} s")
    slots = server.params.slots
    for seed in [int(s) for s in args.seeds.split(",")]:
        w = harness.run_window(server, seed, args.seconds, counter)
        got, bad = harness.check_window(cell, w, slots)
        walls = sorted(b.wall_s for b in w.batches)
        row = {"seed": seed, "requests": w.attempted,
               "batches": len(walls), "batch_wall_median": walls[len(walls) // 2],
               "batch_wall_max": walls[-1],
               "unserved": len(w.unserved), "compiles": w.compiles,
               "program": got,
               "control": {r: control_reading(cell, w, slots, r)
                           for r in RUNGS}}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
