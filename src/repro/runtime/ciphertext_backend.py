"""CiphertextBackend: the serving backend that actually encrypts.

Third executor backend behind the ``execute(schedule, batch, ...) ->
seconds`` contract (see runtime/executor.py): where AnalyticBackend
prices a batch on the MemoryModel and MeshBackend streams
shape-preserving placeholder stages over a device mesh, this backend
runs the compiled `PipelineSchedule` on *actually encrypted* data
through the real CKKS stack, via the batched schedule-evaluation
engine (repro/compiler/engine.py) shared with the compiler's
verification tests.

Per batch:

* requests' slot groups are packed into (B, slots) value rows exactly
  like the mesh backend packs microbatches, then encrypted under the
  engine's secret key — the runtime owns the ingress encryptor, so
  plaintext payloads never travel past this point;
* every trace op executes as ONE vmapped dispatch covering the whole
  ciphertext stack (batched key-switch digits included);
* stage constants are encoded once and reused across batches through
  the runtime `KeyCache` (real residency accounting: evk/Galois-key
  footprints are pinned, plaintext constants LRU-evictable);
* outputs are decrypted and compared against the plaintext oracle
  (`reference_eval`) on the same packed values — max |error| lands in
  ``MetricsRegistry.decrypt_error`` next to the latency percentiles;
* per-stage wall times (completion barrier per stage) accumulate in
  ``stage_stats`` — the measured side of benchmarks/fig18_calibration;
* every layer boundary (``pack``, ``encrypt``, ``stage``, ``decode``
  with ``decrypt``/``lift``/``embed``, ``check``) goes
  through the one hook of `repro.obs.hook`, which feeds the profiler,
  the always-on ring, the tracer and telemetry from one measurement;
  building a backend installs its garbage-collection listener.

Workload inputs beyond the request payload (e.g. HELR's weight vector)
and the named plaintext constants are synthesized deterministically per
(workload, name) — they play the role of server-side model state.
"""
from __future__ import annotations

import time
import zlib
from typing import Dict, Iterable, List, Optional, Set, Tuple

import numpy as np

from repro.compiler.engine import CkksEngine, op_cexpr
from repro.compiler.interp import reference_eval
from repro.core.params import CkksParams
from repro.core.pipeline import PipelineSchedule
from repro.core.trace import FheTrace
from repro.obs import hook
from repro.obs.hook import layer
from repro.runtime.batcher import Batch
from repro.runtime.keycache import KeyCache
from repro.runtime.metrics import MetricsRegistry


def base_const_names(trace: FheTrace) -> List[str]:
    """Named plaintext constants a trace's pmul/padd ops reference,
    including through derived const expressions (ir.py cexprs)."""
    names: Set[str] = set()

    def walk(expr):
        if expr[0] == "ref":
            names.add(expr[1])
        elif expr[0] == "rot":
            walk(expr[1])
        else:
            walk(expr[1])
            walk(expr[2])

    for op in trace.ops:
        if op.kind in ("pmul", "padd"):
            walk(op_cexpr(op))
    return sorted(names)


def _stable_rng(*parts: str) -> np.random.Generator:
    seed = zlib.crc32("/".join(parts).encode()) & 0xFFFFFFFF
    return np.random.default_rng(seed)


class _StageStat:
    """Running mean of one stage's measured wall seconds."""

    __slots__ = ("total_s", "count")

    def __init__(self):
        self.total_s = 0.0
        self.count = 0

    def add(self, seconds: float) -> None:
        self.total_s += seconds
        self.count += 1

    @property
    def mean_s(self) -> float:
        return self.total_s / self.count if self.count else 0.0


class CiphertextBackend:
    """Real encrypted execution of compiled schedules, batched."""

    def __init__(self, params: CkksParams, seed: int = 7,
                 use_kernels: Optional[bool] = None,
                 const_amplitude: float = 0.25):
        import jax
        if use_kernels is None:
            # the Pallas kernel route (fused keyswitch + modmul) compiles
            # natively on TPU; interpret mode elsewhere is correct but
            # slower than the library path
            use_kernels = jax.default_backend() == "tpu"
        self.use_kernels = bool(use_kernels)
        self._key_cache: Optional[KeyCache] = None
        self._local_consts: Dict = {}
        self._consts_memo: Dict[Tuple, Dict[str, np.ndarray]] = {}
        self._aux_memo: Dict[Tuple[str, int], np.ndarray] = {}
        self.const_amplitude = const_amplitude
        self.engine = CkksEngine(params, seed=seed,
                                 const_cache=self._cached_const,
                                 on_key_load=self._on_key_load,
                                 use_kernels=use_kernels)
        hook.install_gc_listener()
        # workload -> per-stage running means of measured seconds
        self.stage_stats: Dict[str, List[_StageStat]] = {}
        self.pad_batch_to: Optional[int] = None   # bucketing (executor sets)

    # -- KeyCache integration ------------------------------------------------

    def _cached_const(self, key, nbytes: int, loader):
        """Engine const hook: memoize encoded plaintexts through the
        runtime KeyCache when one is wired, else a local dict."""
        if self._key_cache is None:
            if key not in self._local_consts:
                self._local_consts[key] = loader()
            return self._local_consts[key]
        value, _hit, _load_s = self._key_cache.get_or_load(
            key, nbytes, loader=loader)
        return value

    def _on_key_load(self, key: Tuple, nbytes: int) -> None:
        """Evaluation keys (relin / Galois) and bootstrap plaintexts are
        pinned residents: a serving system cannot evict them mid-flight."""
        if self._key_cache is not None:
            self._key_cache.get_or_load(("engine",) + key, nbytes, pin=True)

    @staticmethod
    def pinned_key_bytes(traces: Iterable[FheTrace], params: CkksParams,
                         pass_config=None) -> Tuple[int, int]:
        """(evaluation keys, bytes) this backend pins to serve ``traces``
        as the compiler emits them under ``pass_config``: the
        relinearization key plus one Galois key per element the engine
        switches to (`engine.galois_element`); where a trace bootstraps
        on a parameter set with bootstrap settings, also the bootstrap's
        Galois and conjugation keys and, in the bytes, its DFT
        plaintexts at each level it refreshes to."""
        from repro.compiler import optimize_trace
        from repro.compiler.engine import galois_element
        from repro.core.bootstrap import bootstrap_plan
        from repro.core.trace import evk_bytes
        elts, targets = set(), set()
        for trace in traces:
            if pass_config is not None:
                trace, _ = optimize_trace(trace, params, pass_config)
            elts.update(galois_element(op, params) for op in trace.ops)
            targets.update(op.level for op in trace.ops
                           if op.kind == "bootstrap")
        elts.discard(None)
        pt_bytes = 0
        if targets and params.has_bootstrap:
            plan = bootstrap_plan(params)
            elts.update(plan.galois_elements())
            pt_bytes = sum(plan.plaintext_limbs(params.bts_raise_level(t))
                           for t in targets) * params.n * 8
        n_keys = 1 + len(elts)
        return n_keys, n_keys * evk_bytes(params) + pt_bytes

    def _sync_keys(self) -> None:
        """Register what the engine already holds for good (evaluation
        keys, bootstrap plaintexts) into the wired KeyCache (pinned).
        Keys may have been generated before this cache was attached —
        residency accounting must not depend on generation timing. Only
        MISSING entries are loaded: pinned entries never leave, and
        re-touching them every batch would inflate the hit-rate metrics
        the serving sweeps report."""
        if self._key_cache is None:
            return
        for key, nb in self.engine.pinned():
            key = ("engine",) + key
            if key not in self._key_cache:
                self._key_cache.get_or_load(key, nb, pin=True)

    # -- deterministic server-side state -------------------------------------

    def workload_consts(self, workload: str,
                        trace: FheTrace) -> Dict[str, np.ndarray]:
        """Memoized per (workload, const-name set): each value is a pure
        function of (workload, name), so reuse across traces of one
        workload is exact — and synthesis stays out of the timed
        service window."""
        key = (workload, tuple(base_const_names(trace)))
        consts = self._consts_memo.get(key)
        if consts is None:
            slots = self.engine.params.slots
            consts = self._consts_memo[key] = {
                name: self.const_amplitude
                * _stable_rng(workload, "const", name).standard_normal(slots)
                for name in key[1]}
        return consts

    def _aux_input(self, workload: str, input_pos: int,
                   batch_size: int) -> np.ndarray:
        """Inputs past the payload slot (weights etc.): one deterministic
        vector (memoized) broadcast across the batch."""
        v = self._aux_memo.get((workload, input_pos))
        if v is None:
            slots = self.engine.params.slots
            v = self._aux_memo[(workload, input_pos)] = \
                self.const_amplitude * _stable_rng(
                    workload, "input", str(input_pos)).standard_normal(slots)
        return np.broadcast_to(v, (batch_size, len(v)))

    def _pack(self, batch: Batch, n_micro: int) -> np.ndarray:
        """Requests' payload values -> (n_micro, slots) rows, mirroring
        MeshBackend._pack (each request owns a contiguous slot range)."""
        slots = self.engine.params.slots
        x = np.zeros((n_micro, slots), dtype=np.complex128)
        for ct_i, group in enumerate(batch.slot_groups):
            off = 0
            for r in group:
                n = r.slots_needed
                if r.payload is not None:
                    try:
                        v = np.asarray(r.payload,
                                       dtype=np.complex128).ravel()[:n]
                    except (TypeError, ValueError):
                        v = None   # opaque payload: slots stay zero
                    if v is not None:
                        x[ct_i, off:off + len(v)] = v
                off += n
        return x

    # -- execution -----------------------------------------------------------

    def execute(self, schedule: PipelineSchedule, batch: Batch, *,
                key_cache: Optional[KeyCache],
                metrics: MetricsRegistry, workload: str,
                obs=None) -> float:
        trace = schedule.trace
        assert trace is not None, "mapper did not attach the trace"
        self._key_cache = key_cache
        self._sync_keys()
        n_micro = max(self.pad_batch_to or 0, batch.n_ciphertexts, 1)

        with hook.batch(obs, metrics.telemetry) as b:
            with layer("pack", cts=n_micro, requests=len(batch.requests)):
                values = self._pack(batch, n_micro)
                inputs = [values] + [self._aux_input(workload, i, n_micro)
                                     for i in range(1, len(trace.inputs))]
                consts = self.workload_consts(workload, trace)
            outs, stage_s = self.engine.run_schedule(
                schedule, inputs, consts, const_scope=(workload,))
            dt = time.perf_counter() - b.t_start

            # decrypt-side accuracy vs the plaintext oracle on the very
            # same packed values (reference_eval resolves derived cexprs
            # too); outside the returned service seconds, so the next
            # batch starts at its start on the executor's timeline: its
            # span goes on a track of its own
            with layer("check", track="host:check", cts=n_micro):
                ref = reference_eval(trace, inputs, consts)
                err = max(float(np.abs(np.asarray(d) - np.asarray(r)).max())
                          for d, r in zip(outs, ref)) if outs else 0.0
                metrics.observe_decrypt_error(workload, err)

        stats = self.stage_stats.setdefault(
            workload, [_StageStat() for _ in schedule.stages])
        if len(stats) != len(schedule.stages):   # recompiled differently
            stats = self.stage_stats[workload] = \
                [_StageStat() for _ in schedule.stages]
        for st, sec in zip(schedule.stages, stage_s):
            stats[st.idx].add(sec)
            metrics.occupancy.add(st.partition, sec)
        batch.outputs = outs
        return dt

    # -- calibration hooks ---------------------------------------------------

    def measured_stage_seconds(self, workload: str) -> List[float]:
        """Mean measured wall seconds per stage (fig18's measured side)."""
        return [s.mean_s for s in self.stage_stats.get(workload, [])]

    @property
    def tolerance(self) -> float:
        return self.engine.tolerance
