"""Multi-tenant FHE serving driver over repro.runtime.

Synthetic tenants submit encrypted requests against registered FHE
workloads; the runtime batches them into slot groups, keeps stage
constants resident in the key cache, and drains them through the
load-save pipeline. Reports latency percentiles, throughput, cache
hit rates, and (on the ciphertext backend) per-workload decrypt
accuracy.

Backends: ``analytic`` (MemoryModel cost model, virtual clock),
``mesh`` (distributed placeholder stages, wall clock), ``ciphertext``
(REAL encrypted execution through the batched CKKS engine — the run
fails if any workload's max |decrypt error| exceeds the parameter
set's CKKS tolerance), ``pim`` (discrete-event simulation of the
hierarchical FHEmem hardware model, repro.pim; pick the hardware
point with ``--pim-preset``).

``--mem-profile {flat,fhemem,hbm2}`` selects the memory model the
mapper and analytic backend price against from the SAME preset
registry the pim backend's hardware points come from
(repro.pim.arch) — with ``--backend pim`` it defaults to the pim
preset, so both sides of the fig19 comparison share one set of
constants.

    PYTHONPATH=src python -m repro.launch.serve_fhe --smoke
    PYTHONPATH=src python -m repro.launch.serve_fhe --smoke \
        --backend ciphertext
    PYTHONPATH=src python -m repro.launch.serve_fhe --smoke \
        --backend pim --pim-preset fhemem
    PYTHONPATH=src python -m repro.launch.serve_fhe --backend mesh \
        --tenants 4 --requests 64 --rate 2000
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from repro.compiler import PassConfig
from repro.core.params import CkksParams, test_params
from repro.core.pipeline import MemoryModel
from repro.core.trace import FheTrace, LevelBudgetExhausted
from repro.pim.arch import PRESETS as PIM_PRESETS
from repro.pim.arch import memory_model as pim_memory_model
from repro.fleet.router import POLICIES as ROUTER_POLICIES
from repro.runtime import (BatchPolicy, KeyCache, PipelinedExecutor,
                           Request)


from repro.runtime.workloads import (HELR_CONSTS, LOLA_CONSTS, lola_infer,
                                     make_helr_iter, make_matvec,
                                     make_poly_eval, matvec_consts,
                                     poly_consts)

WORKLOADS = {
    "helr": (make_helr_iter(), 2, HELR_CONSTS),
    "lola": (lola_infer, 1, LOLA_CONSTS),
    # rotation-heavy: the compiler's BSGS + lazy-rescale showcase
    "matvec": (make_matvec(16), 1, matvec_consts(16)),
    # deeper than the smoke start level: needs bootstrap insertion
    "poly": (make_poly_eval(12), 1, poly_consts(12)),
}


def workload_traces(start_level: int, pass_config: Optional[PassConfig],
                    quiet: bool = False) -> Dict[str, FheTrace]:
    """The registered workloads' traces at ``start_level``; a workload
    deeper than that without bootstrap insertion is skipped (with a note
    unless ``quiet``)."""
    from repro.runtime.executor import workload_trace
    out = {}
    for name, (fn, n_in, consts) in WORKLOADS.items():
        try:
            out[name] = workload_trace(fn, n_in, consts, start_level,
                                       pass_config)
        except LevelBudgetExhausted:
            if not quiet:
                print(f"skipping workload {name!r}: deeper than "
                      f"start_level={start_level} and --no-opt "
                      f"disables automatic bootstrap insertion")
    return out


def build_executor(params: CkksParams, mem: MemoryModel, *,
                   backend_name: str, max_batch: int, max_wait_s: float,
                   cache_bytes: int, start_level: int,
                   opt: bool = True,
                   use_kernels: bool = None,
                   verify: bool = False) -> PipelinedExecutor:
    from repro.runtime.executor import resolve_backend
    policy = BatchPolicy(slots_per_ct=params.slots, max_batch=max_batch,
                         max_wait_s=max_wait_s)
    key_cache = (KeyCache(cache_bytes, load_bw=mem.load_bw)
                 if cache_bytes > 0 else None)
    backend = resolve_backend(backend_name, params, mem,
                              use_kernels=use_kernels, verify=verify)
    ex = PipelinedExecutor(params, mem, backend=backend, policy=policy,
                           key_cache=key_cache,
                           pass_config=PassConfig() if opt else None,
                           verify=verify)
    for name, trace in workload_traces(start_level, ex.pass_config).items():
        ex.register_trace(name, trace)
    return ex


def build_fleet_scheduler(params: CkksParams, mem: MemoryModel, *,
                          n_devices: int, backend_name: str, router: str,
                          max_batch: int, max_wait_s: float,
                          cache_bytes: int, start_level: int,
                          opt: bool = True, continuous_batching: bool = False,
                          preempt: bool = False, use_kernels: bool = None,
                          verify: bool = False):
    """Fleet-mode mirror of build_executor: N devices (each with its own
    backend instance and caches), one router, one scheduler."""
    from repro.fleet import FleetScheduler
    from repro.runtime.executor import resolve_backend
    policy = BatchPolicy(slots_per_ct=params.slots, max_batch=max_batch,
                         max_wait_s=max_wait_s)

    def backend_factory():
        return resolve_backend(backend_name, params, mem,
                               use_kernels=use_kernels, verify=verify)
    fleet = FleetScheduler(
        params, mem, n_devices=n_devices, backend=backend_factory,
        router=router, policy=policy, cache_bytes=cache_bytes,
        pass_config=PassConfig() if opt else None,
        continuous_batching=continuous_batching, preempt=preempt,
        verify=verify)
    for name, trace in workload_traces(start_level,
                                       fleet.pass_config).items():
        fleet.register_trace(name, trace)
    return fleet


def synth_arrivals(ex, *, n_tenants: int, n_requests: int,
                   rate_rps: float, seed: int, deadline_s: float,
                   encrypt: bool, max_slots: int) -> list:
    """Poisson arrivals from round-robin tenants, alternating workloads.

    With ``encrypt``, each request carries a REAL CKKS ciphertext
    (public-key encryption of a random slot vector on a small
    parameter set) — the runtime never sees plaintext payloads.
    """
    enc = None
    if encrypt:
        from repro.core.context import CkksContext
        from repro.core.encoder import CkksEncoder
        from repro.core.encryptor import CkksEncryptor
        from repro.core.ciphertext import Plaintext
        p_enc = test_params(log_n=8, n_levels=2, dnum=1)
        ctx = CkksContext(p_enc)
        encoder = CkksEncoder(ctx)
        encryptor = CkksEncryptor(ctx, seed=seed)
        sk = encryptor.keygen()
        pk = encryptor.public_keygen(sk)
        scale = float(2 ** p_enc.log_scale)

        def enc(vals):
            pt = Plaintext(encoder.encode(vals, scale, level=1), 1, scale)
            return encryptor.encrypt_pk(pt, pk)

    rng = np.random.default_rng(seed)
    names = list(ex.workloads)
    arrivals = []
    t = 0.0
    for i in range(n_requests):
        t += float(rng.exponential(1.0 / rate_rps))
        slots = int(rng.integers(1, max_slots + 1))
        # bounded payload values keep deep Horner ladders (poly) inside
        # the first-modulus headroom on the real ciphertext backend
        vals = rng.uniform(-0.8, 0.8, size=min(slots, 128))
        payload = vals
        if enc is not None:
            payload = enc(vals)
        arrivals.append(Request(
            ex.next_request_id(),
            tenant=f"tenant{i % n_tenants}",
            workload=names[i % len(names)],
            arrival_s=t, slots_needed=slots,
            deadline_s=t + deadline_s if deadline_s > 0 else None,
            payload=payload))
    return arrivals


# key-cache room for plaintext constants (LRU) beyond the pinned keys
CONST_CACHE_MB = 256


@dataclasses.dataclass
class ServeResult:
    """What one serve_fhe run produced; ``failures`` empty means OK."""
    executor: object
    metrics: object
    params: CkksParams
    warmup_s: float
    serve_s: float
    failures: List[str]


def main(argv: Optional[List[str]] = None) -> None:
    from repro.launch.jax_cache import enable_compile_cache
    enable_compile_cache()
    res = run(argv)
    for f in res.failures:
        print(f"FAILED: {f}", file=sys.stderr)
    if res.failures:
        sys.exit(1)


def run(argv: Optional[List[str]] = None) -> ServeResult:
    """Parse serve_fhe's arguments, serve, and check the outcome."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small params, few requests, fast end-to-end check")
    ap.add_argument("--backend",
                    choices=("analytic", "mesh", "ciphertext", "pim"),
                    default="analytic")
    ap.add_argument("--pim-preset", choices=sorted(PIM_PRESETS),
                    default="fhemem",
                    help="hardware point for --backend pim "
                         "(repro.pim.arch presets)")
    ap.add_argument("--mem-profile", choices=sorted(PIM_PRESETS),
                    default=None,
                    help="price the pipeline against this preset's "
                         "memory model instead of the built-in "
                         "defaults (shared registry with the pim "
                         "backend; defaults to --pim-preset when "
                         "--backend pim)")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="serve on a simulated fleet of N devices "
                         "(repro.fleet), each wrapping its own "
                         "--backend instance; 0 = single executor")
    ap.add_argument("--router", choices=ROUTER_POLICIES,
                    default="round_robin",
                    help="fleet admission-time placement policy")
    ap.add_argument("--continuous-batching", action="store_true",
                    help="fleet: refill free slot rows of in-flight "
                         "batches between pipeline rounds")
    ap.add_argument("--preempt", action="store_true",
                    help="fleet: preempt best-effort batches at round "
                         "boundaries when a deadline batch is ready")
    ap.add_argument("--tenants", type=int, default=3)
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--rate", type=float, default=5000.0,
                    help="offered load, requests/s (aggregate)")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--deadline-ms", type=float, default=200.0,
                    help="per-request deadline; 0 disables")
    ap.add_argument("--cache-mb", type=int, default=None,
                    help="key cache capacity; 0 disables the cache. "
                         "Default: 256, and for --backend ciphertext the "
                         "pinned evaluation keys of the registered "
                         "workloads plus 256 (a smaller explicit value "
                         "fails at startup with the size needed)")
    ap.add_argument("--no-encrypt", action="store_true",
                    help="skip real CKKS payload encryption at ingest")
    ap.add_argument("--use-kernels", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="(--backend ciphertext) route keyswitch + modmul "
                         "through the fused Pallas kernels "
                         "(repro.kernels.keyswitch; bit-exact vs the "
                         "library path); the kernels compile on a TPU and "
                         "run in interpret mode anywhere else; default: "
                         "on iff running on a TPU")
    ap.add_argument("--verify", action="store_true",
                    help="static verification (repro.analysis): sweep "
                         "every freshly compiled schedule (per-pass "
                         "diffs, trace/schedule invariants) and — with "
                         "--backend pim — hazard-analyze every lowered "
                         "instruction stream; an error finding aborts "
                         "instead of serving a corrupt artifact")
    ap.add_argument("--opt", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="run the optimizing trace compiler "
                         "(repro.compiler) before pipeline mapping; "
                         "--no-opt serves every trace verbatim")
    ap.add_argument("--trace-out", metavar="PATH", default=None,
                    help="record per-request span trees (repro.obs) and "
                         "write a Chrome/Perfetto trace_event JSON here "
                         "(load in https://ui.perfetto.dev or "
                         "chrome://tracing); one track per device, one "
                         "per tenant")
    ap.add_argument("--metrics-out", metavar="PATH", default=None,
                    help="sample time-series telemetry (repro.obs."
                         "telemetry: per-bank PIM utilization, queue "
                         "depths, goodput, SLO burn rates) during the "
                         "serve and write an OpenMetrics/Prometheus "
                         "exposition here (self-validated; inspect "
                         "with any promtool-compatible reader)")
    ap.add_argument("--log-json", action="store_true",
                    help="emit one JSON line per request lifecycle "
                         "event (accepted/routed/preempted/completed/"
                         "dropped...) to stdout as it happens")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.smoke:
        args.requests = min(args.requests, 60)
        params = test_params(log_n=10, n_levels=8, dnum=2)
        start_level = 7
        mem = MemoryModel(n_partitions=4, partition_bytes=8 * 2 ** 20)
        if args.backend == "ciphertext":
            # real homomorphic execution on CPU: fewer requests, a
            # smaller ring, and no deadlines (wall-clock batches would
            # expire a 200ms budget spuriously on slow runners)
            args.requests = min(args.requests, 24)
            params = test_params(log_n=8, n_levels=8, dnum=2)
            args.deadline_ms = 0.0
    else:
        if args.backend == "ciphertext":
            import jax
            if jax.default_backend() != "tpu":
                ap.error("--backend ciphertext at the paper's parameters "
                         "(logN=16) runs on a TPU, and JAX found none "
                         f"(default backend: {jax.default_backend()}); "
                         "--smoke is the CPU-sized check")
        from repro.core.params import paper_params_bootstrap
        params = paper_params_bootstrap()
        start_level = 20
        mem = MemoryModel(n_partitions=16, partition_bytes=96 * 2 ** 20)

    # shared preset registry (repro.pim.arch): the pim backend recovers
    # its arch from the mem via resolve_backend, so pricing and DES use
    # the same hardware point by construction — which also means the
    # two flags cannot name different points
    profile = args.mem_profile
    if args.backend == "pim":
        if profile is not None and profile != args.pim_preset:
            ap.error(f"--backend pim derives its hardware point from "
                     f"the memory model, so --mem-profile {profile!r} "
                     f"would silently override --pim-preset "
                     f"{args.pim_preset!r}; pass one of them")
        profile = args.pim_preset
    if profile is not None:
        mem = pim_memory_model(profile)

    # the ciphertext backend owns the ingress encryptor (payload values
    # are encrypted under the serving keys at pack time), so the
    # synthetic foreign-key ciphertext wrapping is redundant there
    encrypt = not args.no_encrypt and args.backend != "ciphertext"
    cache_mb = CONST_CACHE_MB if args.cache_mb is None else args.cache_mb
    if args.backend == "ciphertext" and cache_mb > 0:
        # every evaluation key is pinned: size the cache for them (or
        # refuse an explicit size that cannot hold them) before warmup
        from repro.runtime.ciphertext_backend import CiphertextBackend
        pass_config = PassConfig() if args.opt else None
        n_keys, key_bytes = CiphertextBackend.pinned_key_bytes(
            workload_traces(start_level, pass_config, quiet=True).values(),
            params, pass_config)
        need_mb = -(-key_bytes // 2 ** 20)
        if args.cache_mb is None:
            cache_mb = need_mb + CONST_CACHE_MB
        elif cache_mb < need_mb:
            ap.error(f"--cache-mb {cache_mb} cannot hold the {n_keys} "
                     f"evaluation keys the registered workloads pin "
                     f"({need_mb} MiB at logN={params.log_n}); pass "
                     f"--cache-mb {need_mb + CONST_CACHE_MB} or more")
    if args.fleet > 0:
        ex = build_fleet_scheduler(
            params, mem, n_devices=args.fleet, backend_name=args.backend,
            router=args.router, max_batch=args.max_batch,
            max_wait_s=args.max_wait_ms * 1e-3,
            cache_bytes=cache_mb * 2 ** 20,
            start_level=start_level, opt=args.opt,
            continuous_batching=args.continuous_batching,
            preempt=args.preempt, use_kernels=args.use_kernels,
            verify=args.verify)
    else:
        ex = build_executor(params, mem, backend_name=args.backend,
                            max_batch=args.max_batch,
                            max_wait_s=args.max_wait_ms * 1e-3,
                            cache_bytes=cache_mb * 2 ** 20,
                            start_level=start_level, opt=args.opt,
                            use_kernels=args.use_kernels,
                            verify=args.verify)
    arrivals = synth_arrivals(
        ex, n_tenants=args.tenants, n_requests=args.requests,
        rate_rps=args.rate, seed=args.seed,
        deadline_s=args.deadline_ms * 1e-3,
        encrypt=encrypt, max_slots=min(128, params.slots))

    cache_tag = "off" if cache_mb <= 0 else f"{cache_mb}MiB"
    fleet_tag = (f"fleet of {args.fleet} ({args.router} router"
                 f"{', continuous batching' if args.continuous_batching else ''}"
                 f"{', preemption' if args.preempt else ''}), "
                 if args.fleet > 0 else "")
    print(f"serving {len(arrivals)} requests from {args.tenants} tenants "
          f"({fleet_tag}{args.backend} backend, key cache {cache_tag}, "
          f"compiler {'on' if args.opt else 'off'})")
    t0 = time.perf_counter()
    ex.warmup()
    warmup_s = time.perf_counter() - t0
    print(f"warmup (compile + key preload): {warmup_s:.2f} s")
    # observability: the tracer/event log hang off the shared registry
    # (fleet devices all share ex.metrics), attached after warmup so
    # deploy-time work stays out of the serving trace
    tracer = None
    if args.trace_out:
        from repro.obs import Tracer
        tracer = ex.metrics.tracer = Tracer()
    if args.log_json:
        from repro.obs import JsonEventLog
        ex.metrics.event_log = JsonEventLog(sys.stdout)
    telemetry = None
    if args.metrics_out:
        from repro.obs import SloBurnRate, Telemetry
        wall = args.backend in ("mesh", "ciphertext")
        telemetry = ex.metrics.telemetry = Telemetry(
            clock="wall" if wall else "virtual")
        if args.deadline_ms > 0:
            ex.metrics.slo = SloBurnRate()
    t0 = time.perf_counter()
    m = ex.serve(arrivals)
    serve_s = time.perf_counter() - t0
    print(m.format_table())
    if args.verify:
        # warmup compiles point metrics at a scratch registry, so the
        # durable record is the one riding the cached schedules (and,
        # for pim, the backend's lower-time counters)
        caches = ([d.compile_cache for d in ex.devices]
                  if args.fleet > 0 else [ex.compile_cache])
        backends = ([d.backend for d in ex.devices]
                    if args.fleet > 0 else [ex.backend])
        scheds = [s for c in caches for s in c._cache.values()]
        v_wall = sum(getattr(s, "_verify_wall_s", 0.0) for s in scheds)
        v_find = sum(len(s.verify_report.findings) for s in scheds
                     if getattr(s, "verify_report", None) is not None)
        v_wall += sum(getattr(b, "verify_wall_s", 0.0) for b in backends)
        v_find += sum(getattr(b, "verify_findings", 0) for b in backends)
        print(f"verify: {len(scheds)} schedule(s) + "
              f"{sum(len(getattr(b, '_lowered', ())) for b in backends)} "
              f"lowered program(s) swept, {v_find} finding(s), "
              f"{v_wall * 1e3:.1f} ms wall")
    if tracer is not None:
        from repro.obs import write_trace
        wall = args.backend in ("mesh", "ciphertext")
        obj = write_trace(tracer.store, args.trace_out,
                          clock="wall" if wall else "virtual",
                          telemetry=telemetry)
        print(f"trace: {len(tracer.store)} spans "
              f"({len(obj['traceEvents'])} events"
              + (f", {len(telemetry)} counter tracks"
                 if telemetry is not None else "")
              + f") -> {args.trace_out}")
    if telemetry is not None:
        from repro.obs import parse_openmetrics, write_metrics
        text = write_metrics(args.metrics_out, telemetry, ex.metrics)
        n = len(parse_openmetrics(text)[0])
        slo = ex.metrics.slo
        slo_tag = (f", {len(slo.alerts)} SLO alert(s)"
                   if slo is not None else "")
        print(f"metrics: {len(telemetry)} series "
              f"({telemetry.n_points()} points, {n} samples, "
              f"{telemetry.clock} clock{slo_tag}) -> {args.metrics_out}")

    failures: List[str] = []
    if args.backend == "ciphertext":
        tol = (ex.devices[0].backend if args.fleet > 0
               else ex.backend).tolerance
        for w in ex.workloads:
            err = m.decrypt_error.get(w)
            if err is None:
                print(f"accuracy {w:<12} no batch served FAIL")
                failures.append(f"workload {w!r} served no batch")
                continue
            ok = err <= tol
            if not ok:
                failures.append(f"workload {w!r} max|err|={err:.3e} "
                                f"exceeds tol={tol:.3e}")
            print(f"accuracy {w:<12} batches={m.decrypt_batches[w]} "
                  f"max|err|={err:.3e} tol={tol:.3e} "
                  f"{'OK' if ok else 'FAIL'}")
    return ServeResult(ex, m, params, warmup_s, serve_s, failures)


if __name__ == "__main__":
    main()
