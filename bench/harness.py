"""The benchmark's harness: builds the system under test for one cell,
warms it up, drives its serving loop through a measured window, and checks
what the window served against the plain reference.

The window drives ``PipelinedExecutor.serve`` with a
``CiphertextBackend(params, use_kernels=True)``: admission queue, slot
batcher, compile cache, ``CiphertextBackend.execute``,
``CkksEngine.run_schedule`` and the Pallas kernels, the path that
``serve_fhe --backend ciphertext`` takes. The harness observes it through
wrappers on the backend's and engine's instances, never by editing the
program:

* ``execute``: each batch's wall-clock start and end, its requests and
  slot placement, and its decoded outputs;
* with ``layers`` (the traced run) also ``_pack`` and ``encrypt_batch``
  (host ingress), ``run_schedule`` (the stage seconds it returns),
  ``run_ops`` (one stage), ``decode_batch`` (host egress) and the fused
  keyswitch's ``apply`` (the calls, for its roofline), each inside a
  ``jax.profiler.TraceAnnotation`` named ``bench.<layer>``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import arrivals, reference

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class BenchError(Exception):
    """A run that cannot produce a result (no chip, bad cell, ...)."""


# ---------------------------------------------------------------------------
# cells
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    bench: dict

    @property
    def program(self) -> str:
        return self.mix["program"]

    @property
    def program_spec(self) -> dict:
        return self.config["programs"][self.program]

    @property
    def limit(self) -> float:
        """The limit of the correctness check's number, ``max_abs_err``,
        set for this mix's program and values (PERF.md gives its
        readings)."""
        return float(self.mix["limit_max_abs_err"])


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no cell {name!r} in BENCHMARK.json "
                         f"(cells: {', '.join(sorted(cells))})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(os.path.join(root, configs[w["config"]]["file"]))
    mix = load_json(os.path.join(BENCH, "traffic", f"{w['traffic']}.json"))
    return Cell(name, int(w["chips"]), cfg, mix, bench)


def cell_metrics(cell: Cell, section: str) -> List[dict]:
    """The metrics of ``section`` ('end_to_end' or 'per_layer') that this
    cell reports."""
    return [m for m in cell.bench[section]
            if "workloads" not in m or cell.name in m["workloads"]]


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def require_tpu(chips: int):
    """The TPU devices, or BenchError: there is no CPU fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU found: JAX's first device is "
                         f"{devs[0].platform!r} ({devs[0].device_kind}); "
                         f"this benchmark runs on a TPU only")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def enable_cache() -> str:
    """JAX's persistent compilation cache in the checkout's fixed
    ``.jax_cache``, handed to the program through the variable it reads;
    every program is cached, however short its compile."""
    import jax
    path = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    from repro.launch.jax_cache import enable_compile_cache
    got = enable_compile_cache()
    if got:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return got


class CompileCounter:
    """Counts XLA backend compiles (a persistent-cache load counts too)
    and persistent-cache hits through jax.monitoring; ``missed`` holds the
    (program, seconds) of each compile the cache did not serve."""

    def __init__(self):
        import jax.monitoring
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.missed: List[tuple] = []
        self._hit = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs
            if not self._hit:
                self.missed.append((kw.get("fun_name", "?"), secs))
            self._hit = False

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
            self._hit = True


# ---------------------------------------------------------------------------
# records
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BatchRec:
    """One batch the window served, as the harness saw it."""
    start: float                    # wall s from window origin
    end: float = 0.0
    service_s: float = 0.0          # what execute returned
    n_ct: int = 0                   # padded ciphertexts
    requests: List[tuple] = dataclasses.field(default_factory=list)
    #                                 (request id, due s, slots)
    slot_groups: List[list] = dataclasses.field(default_factory=list)
    outputs: Optional[np.ndarray] = None
    pack_s: float = 0.0
    encrypt_s: float = 0.0
    stage_s: List[float] = dataclasses.field(default_factory=list)
    decode_s: float = 0.0
    counted: bool = True

    @property
    def wall_s(self) -> float:
        return self.end - self.start

    @property
    def stages_s(self) -> float:
        return sum(self.stage_s)

    @property
    def ingress_s(self) -> float:
        return self.pack_s + self.encrypt_s

    @property
    def egress_s(self) -> float:
        return self.decode_s + max(0.0, self.wall_s - self.service_s)


@dataclasses.dataclass
class Window:
    """What one measured window produced."""
    seed: int
    seconds: float
    batches: List[BatchRec]
    payloads: Dict[int, np.ndarray]
    due: Dict[int, float]           # request id -> due s (window origin)
    attempted: int
    unserved: List[int]
    wall_s: float                   # window origin to last batch end
    compiles: int
    generator_late_s: float
    ks_calls: List[tuple]           # (batch, level) per fused keyswitch
    origin: float = 0.0             # perf_counter of the window origin
    layers: bool = False            # per-layer times were taken
    trace: Optional[object] = None  # bench.trace.Summary of a traced run

    @property
    def counted(self) -> List[BatchRec]:
        return [b for b in self.batches if b.counted]


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def _annotate(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class Server:
    """One cell's executor, built as ``serve_fhe.build_executor`` builds
    it, with only the cell's own program registered and the key cache
    sized for its pinned evaluation keys."""

    def __init__(self, cell: Cell, *, layers: bool = False):
        from repro.compiler import PassConfig
        from repro.core.params import CkksParams
        from repro.core.pipeline import MemoryModel
        from repro.launch.serve_fhe import CONST_CACHE_MB
        from repro.runtime import (BatchPolicy, KeyCache, PipelinedExecutor)
        from repro.runtime import workloads
        from repro.runtime.ciphertext_backend import CiphertextBackend
        from repro.runtime.executor import workload_trace

        self.cell = cell
        self.layers = layers
        cfg, mix, spec = cell.config, cell.mix, cell.program_spec
        self.params = CkksParams(**cfg["params"])
        mem = MemoryModel(**cfg["memory_model"])
        fn = getattr(workloads, spec["factory"])
        if "args" in spec:
            fn = fn(*spec["args"])
        consts = spec["consts"]
        if isinstance(consts, str):
            consts = getattr(workloads, consts)(*spec.get("consts_args", []))
        pass_config = PassConfig()
        trace = workload_trace(fn, int(spec["inputs"]), tuple(consts),
                               int(cfg["start_level"]), pass_config)
        _, key_bytes = CiphertextBackend.pinned_key_bytes(
            [trace], self.params, pass_config)
        policy = BatchPolicy(slots_per_ct=self.params.slots,
                             max_batch=int(mix["max_batch"]),
                             max_wait_s=float(mix["max_wait_ms"]) * 1e-3)
        self.backend = CiphertextBackend(self.params, use_kernels=True)
        self.ex = PipelinedExecutor(
            self.params, mem, backend=self.backend, policy=policy,
            key_cache=KeyCache(key_bytes + CONST_CACHE_MB * 2 ** 20,
                               load_bw=mem.load_bw),
            pass_config=pass_config)
        self.ex.register_trace(cell.program, trace)
        self.schedule = None
        self.recording = False
        self.batches: List[BatchRec] = []
        self.ks_calls: List[tuple] = []
        self.origin = 0.0
        self.after_batch: Optional[Callable[[BatchRec], None]] = None
        self._cur: Optional[BatchRec] = None
        self._check_ann = None
        self._wrap()

    # -- instance wrappers ---------------------------------------------------

    def _wrap(self) -> None:
        be, eng = self.backend, self.backend.engine
        execute = be.execute

        def timed_execute(schedule, batch, **kw):
            self.schedule = schedule
            rec = BatchRec(start=time.perf_counter() - self.origin)
            self._cur = rec
            dt = execute(schedule, batch, **kw)
            if self._check_ann is not None:
                self._check_ann.__exit__(None, None, None)
                self._check_ann = None
            rec.end = time.perf_counter() - self.origin
            rec.service_s = dt
            self._cur = None
            if not self.recording:
                return dt
            rec.n_ct = max(be.pad_batch_to or 0, batch.n_ciphertexts, 1)
            rec.requests = [(r.request_id, r.arrival_s, r.slots_needed)
                            for r in batch.requests]
            rec.slot_groups = [[(r.request_id, r.slots_needed) for r in g]
                               for g in batch.slot_groups]
            rec.outputs = np.asarray(batch.outputs[0])
            self.batches.append(rec)
            if self.after_batch is not None:
                self.after_batch(rec)
            return dt
        be.execute = timed_execute
        if not self.layers:
            return

        def timed(fn, field, ann, block=False):
            def wrapper(*a, **kw):
                t = time.perf_counter()
                with _annotate(ann, True):
                    out = fn(*a, **kw)
                    if block:
                        import jax
                        jax.block_until_ready(out.data)
                rec = self._cur
                if rec is not None:
                    setattr(rec, field, getattr(rec, field)
                            + time.perf_counter() - t)
                return out
            return wrapper
        be._pack = timed(be._pack, "pack_s", "bench.pack")
        eng.encrypt_batch = timed(eng.encrypt_batch, "encrypt_s",
                                  "bench.encrypt", block=True)
        eng.decode_batch = timed(eng.decode_batch, "decode_s",
                                 "bench.decode")
        run_ops, run_schedule = eng.run_ops, eng.run_schedule

        def annotated_run_ops(*a, **kw):
            with _annotate("bench.stage", True):
                return run_ops(*a, **kw)
        eng.run_ops = annotated_run_ops

        def staged_run_schedule(*a, **kw):
            outs, stage_s = run_schedule(*a, **kw)
            if self._cur is not None:
                self._cur.stage_s = list(stage_s)
            # what execute does after run_schedule returns: the inline
            # decrypt check against the program's own oracle
            self._check_ann = _annotate("bench.check", True)
            self._check_ann.__enter__()
            return outs, stage_s
        eng.run_schedule = staged_run_schedule
        fks = eng.fused_ks
        apply = fks.apply

        def counted_apply(d2, level, *a, **kw):
            if self.recording:
                self.ks_calls.append((int(d2.shape[0]), int(level)))
            return apply(d2, level, *a, **kw)
        fks.apply = counted_apply

    # -- set-up --------------------------------------------------------------

    def warmup(self) -> None:
        """Compile and load every program the cell's batches use, make its
        keys and encode its constants (one padded batch through execute)."""
        self.ex.warmup()

    # -- windows -------------------------------------------------------------

    def _begin(self) -> None:
        self.batches = []
        self.ks_calls = []
        self.recording = True
        self.origin = time.perf_counter()

    def _request(self, p: arrivals.Planned, arrival_s: float):
        from repro.runtime import Request
        return Request(self.ex.next_request_id(), p.tenant, self.cell.program,
                       arrival_s=arrival_s, slots_needed=p.slots,
                       payload=p.payload)

    def run_open(self, planned: List[arrivals.Planned],
                 trace_on: bool = False):
        """Hand ``serve`` each request when it falls due on the wall clock,
        with its due time as arrival; returns (payloads, due, late_s)."""
        reqs = [self._request(p, p.due_s) for p in planned]
        payloads = {r.request_id: p.payload for r, p in zip(reqs, planned)}
        due = {r.request_id: r.arrival_s for r in reqs}
        late = 0.0
        self._begin()
        i, n = 0, len(reqs)
        idle = True
        while i < n:
            now = time.perf_counter() - self.origin
            if reqs[i].arrival_s > now:
                with _annotate("bench.wait", trace_on):
                    time.sleep(reqs[i].arrival_s - now)
                idle = True
                continue
            j = i
            while j < n and reqs[j].arrival_s <= now:
                j += 1
            if idle:
                late = max(late, now - reqs[j - 1].arrival_s)
            idle = False
            with _annotate("bench.serve", trace_on):
                self.ex.serve(reqs[i:j], start_s=now)
            i = j
        self.recording = False
        return payloads, due, late

    def run_closed(self, loop: arrivals.ClosedLoop, seconds: float,
                   trace_on: bool = False):
        """Closed loop: every client's next request goes in when its
        previous one is done; batches that start within ``seconds`` of the
        first batch count, and the clients leave when one ends after it."""
        payloads: Dict[int, np.ndarray] = {}
        due: Dict[int, float] = {}
        client: Dict[int, int] = {}
        program = self.cell.program
        start = [None]

        def after(rec: BatchRec) -> None:
            if start[0] is None:
                start[0] = rec.start
            rec.counted = rec.start - start[0] < seconds
            if rec.end - start[0] < seconds:
                for rid, _, _ in rec.requests:
                    p = loop.next(client[rid], rec.end)
                    r = self.ex.submit(p.tenant, program, rec.end,
                                       slots_needed=p.slots,
                                       payload=p.payload)
                    payloads[r.request_id] = p.payload
                    due[r.request_id] = rec.end
                    client[r.request_id] = p.client
            else:
                for q in self.ex.queue.queues.values():
                    q.clear()
        plans = loop.first()
        first = [self._request(p, 0.0) for p in plans]
        for r, p in zip(first, plans):
            payloads[r.request_id] = p.payload
            due[r.request_id] = 0.0
            client[r.request_id] = p.client
        self.after_batch = after
        self._begin()
        try:
            with _annotate("bench.serve", trace_on):
                self.ex.serve(first, start_s=0.0)
        finally:
            self.after_batch = None
            self.recording = False
        return payloads, due, 0.0


def run_window(server: Server, seed: int, seconds: float,
               counter: CompileCounter, trace_dir: Optional[str] = None
               ) -> Window:
    """One measured window of the cell's traffic from ``seed``."""
    mix = server.cell.mix
    trace_on = trace_dir is not None
    if mix["loop"] == "open":
        planned = arrivals.open_loop(mix, seconds, seed)
    elif mix["loop"] == "closed":
        loop = arrivals.ClosedLoop(mix, seed)
    else:
        raise BenchError(f"unknown loop {mix['loop']!r}")
    n_compiles = counter.n
    if trace_on:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1     # the bench.* annotations only
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        if mix["loop"] == "open":
            payloads, due, late = server.run_open(planned, trace_on)
        else:
            payloads, due, late = server.run_closed(loop, seconds, trace_on)
    finally:
        if trace_on:
            import jax
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            log(f"trace: stop_trace {time.perf_counter() - t_stop:.3f} s")
    compiles = counter.n - n_compiles
    batches = server.batches
    served = {rid for b in batches for rid, _, _ in b.requests}
    if mix["loop"] == "open":
        attempted = list(due)
    else:
        attempted = [rid for b in batches if b.counted
                     for rid, _, _ in b.requests]
    unserved = [rid for rid in attempted if rid not in served]
    wall = max((b.end for b in batches), default=0.0)
    w = Window(seed, seconds, list(batches), payloads, due, len(attempted),
               unserved, wall, compiles, late, list(server.ks_calls),
               origin=server.origin, layers=server.layers)
    if trace_on:
        from bench import trace as tr
        t_red = time.perf_counter()
        w.trace = tr.reduce_file(trace_dir)
        log(f"trace: reduced in {time.perf_counter() - t_red:.3f} s")
    return w


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def check_window(cell: Cell, w: Window, slots: int,
                 dtype=None) -> tuple:
    """Compare every answer the window served with the plain reference
    (or, with ``dtype``, the reference's own output in that precision).
    Returns (widest gap over every served request, failed request ids)."""
    ref = reference.Reference(cell.program, cell.program_spec, slots)
    limit = cell.limit
    worst = 0.0
    bad: List[int] = []
    for b in w.counted:
        rows = reference.pack_rows(b.slot_groups, b.n_ct, slots, w.payloads)
        want = ref.evaluate(rows)
        got = b.outputs if dtype is None else ref.evaluate(rows, dtype)
        for rid, gap in reference.request_errors(b.slot_groups, got, want):
            worst = max(worst, gap)
            if not gap <= limit:
                bad.append(rid)
    return worst, bad


def kernel_dispatch_counts():
    from repro.kernels import common as kcom
    return kcom.dispatch_count(), kcom.interpret_dispatch_count()


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
