"""Chip smoke test: real encrypted serving on one TPU at the paper's parameters.

Runs ``repro.launch.serve_fhe`` in this process, through its own
argument path, with ``--backend ciphertext --use-kernels`` at
``paper_params_bootstrap()`` (logN=16, L=23, dnum=4, start level 20):
a few requests from 2 tenants against every registered workload, with
payloads generated from ``--seed``. It fails (non-zero exit, no result
line) unless

* JAX's first device is a TPU — there is no CPU fallback;
* every workload served at least one batch, and each one's max
  |decrypt error| is within the engine's tolerance;
* the ciphertext backend ran with the Pallas kernels on, the fused
  keyswitch kernels were dispatched, and no kernel ran in interpret
  mode.

The last line of its output is one JSON object naming the device:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.

    python chip_smoke.py [--seed 0] [--requests 8]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


class CompileCounter:
    """Counts XLA backend compiles (and persistent-cache hits) through
    jax.monitoring, with the seconds they took."""

    def __init__(self):
        import collections
        import jax.monitoring
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.by_name = collections.defaultdict(lambda: [0, 0.0])
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs
            entry = self.by_name[kw.get("fun_name", "?")]
            entry[0] += 1
            entry[1] += secs

    def top(self, k: int = 6) -> str:
        rows = sorted(self.by_name.items(), key=lambda kv: -kv[1][1])[:k]
        return ", ".join(f"{name} x{n} {s:.1f}s" for name, (n, s) in rows)

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=8)
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX's first device is {dev.platform!r} "
             f"({dev.device_kind}); this check runs on a TPU only")

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.kernels import common as kcom
    from repro.launch import serve_fhe
    from repro.launch.jax_cache import enable_compile_cache

    cache = enable_compile_cache()
    compiles = CompileCounter()
    print(f"device: {dev.device_kind} ({dev.platform}, "
          f"{len(jax.devices())} device(s)); compile cache {cache}")

    t0 = time.perf_counter()
    res = serve_fhe.run([
        "--backend", "ciphertext", "--use-kernels",
        "--tenants", "2", "--requests", str(args.requests),
        "--max-batch", "2", "--deadline-ms", "0",
        "--seed", str(args.seed)])
    total_s = time.perf_counter() - t0
    p = res.params
    backend = res.executor.backend
    m = res.metrics
    print(f"params: logN={p.log_n} L={p.n_levels} dnum={p.dnum} "
          f"slots={p.slots}")
    print(f"warmup (compile + keys): {res.warmup_s:.3f} s; serve wall "
          f"{res.serve_s:.3f} s; run total {total_s:.3f} s")
    print(f"xla compiles: {compiles.n} ({compiles.cache_hits} from the "
          f"persistent cache), {compiles.seconds:.3f} s; by program: "
          f"{compiles.top()}")
    for w in res.executor.workloads:
        print(f"workload {w}: batches={m.decrypt_batches.get(w, 0)} "
              f"max|err|={m.decrypt_error.get(w, float('nan')):.3e} "
              f"tol={backend.tolerance:.3e}")
    fks = backend.engine._fks
    n_ks = fks.n_applied if fks is not None else 0
    print(f"fused keyswitches: {n_ks}; kernel dispatches: "
          f"{kcom.dispatch_count()} (interpret mode: "
          f"{kcom.interpret_dispatch_count()})")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'n/a')}")

    failures = list(res.failures)
    if not backend.use_kernels:
        failures.append("the ciphertext backend ran without the kernels")
    if n_ks == 0 or kcom.dispatch_count() == 0:
        failures.append("no fused keyswitch kernel was dispatched")
    if kcom.interpret_dispatch_count():
        failures.append(f"{kcom.interpret_dispatch_count()} kernel "
                        f"dispatches ran in interpret mode")
    for w in res.executor.workloads:
        if not m.decrypt_batches.get(w):
            failures.append(f"workload {w!r} served no batch")
    if failures:
        fail("; ".join(dict.fromkeys(failures)))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
