"""One emission hook for the wall-clock serving path.

``layer(name, **attrs)`` wraps one layer boundary of
``CiphertextBackend.execute``, ``CkksEngine.run_schedule`` and the
decode below them. From one pair of ``time.perf_counter`` reads it
feeds four sinks:

1. **profiler** — a ``jax.profiler.TraceAnnotation`` named
   ``fhe.<name>``, so in any profile the program's layers sit on the
   same clock as the device ops (well under a microsecond when no
   profile runs);
2. **ring** (always on) — one `Record` ``(idx, name, start, end, batch,
   attrs)`` in the bounded process-wide `RING`, on ``perf_counter``.
   The ring's anchor pair places any record on the profiler's clock;
3. **tracer** — when the current batch's `ExecObs` carries a
   `Tracer`, a span under the batch span (or the enclosing layer) at
   its true offset on the executor's timeline,
   ``obs.t0 + (perf_counter - execute start)``, on the batch's track
   or on a track of its own (``layer(..., track=...)``);
4. **telemetry** — when ``metrics.telemetry`` is armed, the caller
   reads the exited layer (`layer.seconds`, `layer.end_at`,
   `layer.telemetry`) and feeds its own series, stamped at the
   layer's real end.

``batch(obs, telemetry)`` is the context ``execute`` runs each batch
in: it numbers the batch, re-takes the ring's anchor, and carries the
tracer, telemetry and the execute start to every ``layer`` opened
inside it, however deep. Outside a batch a layer feeds the profiler
and the ring only.

`install_gc_listener` adds `GC` to ``gc.callbacks``: counts and
seconds of every collection by generation, a ring record (and
``fhe.gc`` annotation) for each generation-1 and generation-2 pause,
and, when telemetry is armed, each batch's collections in the
``fhe_gc_collections`` / ``fhe_gc_seconds`` counters by generation.

Always on, with nothing armed: about 2 us a layer (annotation, two
clock reads, a ring append) and a counter bump per generation-0
collection.

The serving path is single-threaded; the ring and the current batch
are process-wide, as ``kernels.common.dispatch_count()`` is.
"""
from __future__ import annotations

import collections
import gc
import itertools
import time
from typing import Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

PREFIX = "fhe."
RING_SIZE = 1 << 16


class Record(NamedTuple):
    idx: int          # position in the ring's stream; the first kept
    #                   record's idx is the number dropped before it
    name: str
    start: float      # perf_counter seconds
    end: float
    batch: int        # sequence number of the enclosing batch, 0 outside
    attrs: Dict[str, object]

    @property
    def seconds(self) -> float:
        return self.end - self.start


class Ring:
    """Bounded in-memory record stream: the newest ``size`` records.

    ``anchor`` pairs one ``perf_counter`` reading with the profiler's
    clock (``time.time_ns``, which a profile's host events are stamped
    on), so `profiler_ns` places any record on a device trace. Every
    batch re-takes it (`reanchor`): a step of the system clock (a
    paused machine, an NTP step) then misplaces only the records taken
    before the step."""

    def __init__(self, size: int = RING_SIZE):
        self.size = size
        self._buf: collections.deque = collections.deque(maxlen=size)
        self._idx = itertools.count()
        self.reanchor()

    def reanchor(self) -> None:
        p0 = time.perf_counter()
        wall_ns = time.time_ns()
        p1 = time.perf_counter()
        self.anchor = ((p0 + p1) / 2, wall_ns)

    def append(self, name: str, start: float, end: float, batch: int,
               attrs: Dict[str, object]) -> None:
        self._buf.append(Record(next(self._idx), name, start, end, batch,
                                attrs))

    def records(self) -> List[Record]:
        """The kept records, in the order they ended."""
        was = gc.isenabled()
        gc.disable()              # a collection's callback appends
        try:
            return list(self._buf)
        finally:
            if was:
                gc.enable()

    @property
    def dropped(self) -> int:
        """Records pushed out of the ring so far."""
        recs = self.records()
        return recs[0].idx if recs else 0

    def profiler_ns(self, t: float) -> int:
        """``perf_counter`` seconds -> the profiler's clock (ns)."""
        p, wall_ns = self.anchor
        return wall_ns + round((t - p) * 1e9)


RING = Ring()


class _Batch:
    """The batch ``execute`` is running: its number, its observers and
    the open tracer spans of its layers."""

    __slots__ = ("seq", "obs", "telemetry", "t_start", "spans", "gc0",
                 "_prev")

    def __init__(self, seq, obs, telemetry):
        self.seq = seq
        self.obs = obs
        self.telemetry = telemetry
        self.spans: List[int] = []

    def at(self, t: float) -> float:
        """``perf_counter`` seconds -> the executor's timeline."""
        return self.obs.t0 + (t - self.t_start)


_seq = itertools.count(1)
_current: Optional[_Batch] = None


class batch:
    """Context of one executed batch (see the module docstring)."""

    __slots__ = ("_b",)

    def __init__(self, obs=None, telemetry=None):
        # series are stamped on the executor's timeline, which obs carries
        self._b = _Batch(next(_seq), obs, telemetry if obs is not None
                         else None)

    def __enter__(self) -> _Batch:
        global _current
        b = self._b
        b._prev, _current = _current, b
        RING.reanchor()
        if b.telemetry is not None:
            b.gc0 = (GC.count[:], GC.seconds[:])
        b.t_start = time.perf_counter()
        return b

    def __exit__(self, *exc) -> bool:
        global _current
        b = self._b
        _current = b._prev
        if b.telemetry is not None:
            GC.feed(b.telemetry, b.at(time.perf_counter()), *b.gc0)
        return False


class layer:
    """One layer boundary (see the module docstring). ``seconds`` holds
    the measured interval once the block has exited. ``track`` puts
    the tracer span on a track of its own, for work the executor's
    timeline does not count (it would overlap the next batch)."""

    __slots__ = ("name", "attrs", "track", "start", "end", "_ann", "_b",
                 "_sid")

    def __init__(self, name: str, *, track: Optional[str] = None,
                 **attrs):
        self.name = name
        self.track = track
        self.attrs = attrs

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def telemetry(self):
        """The armed telemetry of the batch the layer ran in, else None."""
        return self._b.telemetry if self._b is not None else None

    @property
    def end_at(self) -> float:
        """The layer's end on the executor's timeline (inside a batch
        that carries an `ExecObs`)."""
        return self._b.at(self.end)

    def annotate(self, **attrs) -> None:
        """Add ``attrs`` to the layer's ring record and to its tracer
        span, if it has one."""
        self.attrs.update(attrs)
        if self._sid is not None:
            self._b.obs.tracer.store.get(self._sid).attrs.update(attrs)

    def __enter__(self) -> "layer":
        self._ann = TraceAnnotation(PREFIX + self.name)
        self._ann.__enter__()
        b = self._b = _current
        self.start = time.perf_counter()
        if b is not None and b.obs is not None and b.obs.tracer is not None:
            obs = b.obs
            self._sid = obs.tracer.begin(
                self.name, b.at(self.start),
                parent=b.spans[-1] if b.spans else obs.parent,
                track=self.track or obs.track, **self.attrs)
            b.spans.append(self._sid)
        else:
            self._sid = None
        return self

    def __exit__(self, *exc) -> bool:
        end = self.end = time.perf_counter()
        self._ann.__exit__(*exc)
        b = self._b
        RING.append(self.name, self.start, end, b.seq if b else 0,
                    self.attrs)
        if self._sid is not None:
            b.spans.pop()
            b.obs.tracer.end(self._sid, b.at(end))
        return False


class GcStats:
    """``gc.callbacks`` listener: per-generation counts and seconds of
    every collection; generation 1 and 2 pauses also go to the ring
    (and the profiler, as ``fhe.gc``). Generation 0 costs two clock
    reads and two additions. A batch with telemetry armed hands its
    collections on to the series (`feed`)."""

    def __init__(self):
        self.count = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]
        self._t = 0.0
        self._ann = None

    def __call__(self, phase: str, info: dict) -> None:
        gen = info["generation"]
        if phase == "start":
            if gen:
                self._ann = TraceAnnotation(PREFIX + "gc")
                self._ann.__enter__()
            self._t = time.perf_counter()
            return
        t = time.perf_counter()
        self.count[gen] += 1
        self.seconds[gen] += t - self._t
        if gen and self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
            b = _current
            RING.append("gc", self._t, t, b.seq if b else 0,
                        {"generation": gen, "collected": info["collected"]})


    def feed(self, tel, t: float, count0: List[int],
             seconds0: List[float]) -> None:
        """The collections since ``(count0, seconds0)`` into ``tel``'s
        ``fhe_gc_collections`` and ``fhe_gc_seconds`` counters by
        generation, at ``t``."""
        for gen in range(3):
            n = self.count[gen] - count0[gen]
            if n:
                tel.counter("fhe_gc_collections",
                            generation=gen).inc(t, n)
                tel.counter("fhe_gc_seconds", generation=gen).inc(
                    t, self.seconds[gen] - seconds0[gen])


GC = GcStats()


def install_gc_listener() -> GcStats:
    """Add `GC` to ``gc.callbacks`` once per process."""
    if GC not in gc.callbacks:
        gc.callbacks.append(GC)
    return GC
