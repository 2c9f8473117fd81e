"""Run one cell of BENCHMARK.json on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``: a CKKS
parameter set, a start level and the programs it serves) and a traffic mix
(``bench/traffic/<mix>.json``). Set-up builds the serving executor for the
cell's program alone and warms it up; the window then drives real
encrypted serving for ``--seconds``. With ``--trace 0`` the result carries
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics,
read by ``bench/metrics/<name>.py`` from a profiled window.

Every run checks what the window served against the plain reference
(``bench/reference.py``) and prints the numbers compared, each beside its
limit, as the last lines of standard error and under ``checks`` in the
result. The last line of standard output is the result, one JSON object.
Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# import the benchmark as the package ``bench`` (its trace.py must not
# stand in for the standard library's), and the program from src/
if sys.path and os.path.abspath(sys.path[0] or ".") == BENCH:
    sys.path.pop(0)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def measure(cell, *, seed: int, seconds: float, trace: bool, devices,
            t_start: float, server=None, counter=None) -> dict:
    """Set up (unless ``server`` is given), run one window and read the
    cell's metrics; returns the result object."""
    from bench import harness, metrics, roofline
    from bench.harness import log

    if counter is None:
        counter = harness.CompileCounter()
    if server is None:
        server = harness.Server(cell, layers=trace)
        server.warmup()
    slow = sorted(counter.missed, key=lambda m: -m[1])[:5]
    log(f"set-up: {counter.n} XLA compiles ({counter.cache_hits} from the "
        f"persistent cache, {counter.seconds:.3f} s); compiled: "
        f"{len(counter.missed)}, slowest {slow}")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        w = harness.run_window(server, seed, seconds, counter, trace_dir)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    setup_s = w.origin - t_start
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    walls = [b.wall_s for b in w.batches]
    slowest = sorted(range(len(walls)), key=lambda i: -walls[i])[:3]
    log(f"window: {len(w.batches)} batches, {w.attempted} requests, "
        f"{w.wall_s:.3f} s to the last batch end; generator late by at "
        f"most {w.generator_late_s * 1e3:.3f} ms; peak_bytes_in_use {peak}")
    if walls:
        log(f"batch wall s: median {sorted(walls)[len(walls) // 2]:.4f}, "
            f"first {[round(x, 4) for x in walls[:3]]}, slowest "
            f"{[(i, round(walls[i], 4)) for i in slowest]}")

    slots = server.params.slots
    worst, bad = harness.check_window(cell, w, slots)
    n_disp, n_interp = harness.kernel_dispatch_counts()
    checks = [harness.Check("max_abs_err", worst, cell.limit),
              harness.Check("compiles_in_window", w.compiles, 0),
              harness.Check("interpret_dispatches", n_interp, 0),
              harness.Check("unserved_requests", len(w.unserved), 0)]
    if n_disp == 0:
        checks.append(harness.Check("kernel_dispatches_missing", 1, 0))

    run = types.SimpleNamespace(
        cell=cell, window=w, setup_s=setup_s, params=server.params,
        schedule=server.schedule, start_level=int(cell.config["start_level"]),
        peaks=roofline.peaks(devices[0].device_kind))
    section = "per_layer" if trace else "end_to_end"
    out = {}
    for m in harness.cell_metrics(cell, section):
        v = metrics.reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    import jax
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    result = {"correct": all(c.ok for c in checks),
              "attempted": w.attempted,
              "failed": len(w.unserved) + len(bad),
              "metrics": out, "device": device}
    if trace and w.trace is not None:
        device["busy_s"] = w.trace.busy_s
        device["window_s"] = w.trace.window_s
        result["breakdown"] = w.trace.breakdown()
        log(f"trace: busy {w.trace.busy_s:.6f} s of {w.trace.window_s:.6f} "
            f"s; kernels {w.trace.kernel_time_s}; host {w.trace.host_time_s}")
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    for c in checks:
        log(f"check {c.name} {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAILED'}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    try:
        cell = harness.load_cell(args.workload)
        harness.enable_cache()
        devices = harness.require_tpu(cell.chips)
    except (harness.BenchError, ImportError, OSError, KeyError) as e:
        harness.log(f"FAILED: {e}")
        return 1
    harness.log(f"device: {devices[0].device_kind} ({devices[0].platform}, "
                f"{len(devices)} used); compile cache "
                f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')}")
    result = measure(cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), devices=devices,
                     t_start=T_START)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
