"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

* device busy: the union of the intervals in which an op ran on each TPU
  (the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane), averaged over
  the chips;
* kernel device time by kernel: a Pallas kernel runs as a
  ``tpu_custom_call`` op, named in the trace after the jitted function that
  launches it (``%run.3 = ... custom_call_target="tpu_custom_call"`` for
  the fused keyswitch pipeline's ``run``); that name, without its ``%``
  and ``.<n>``, keys the kernel, with the op's output shape;
* idle gaps: the stretches of the window in which no op ran, each named by
  the innermost ``bench.<layer>`` host annotation that covers most of it
  (``host`` where none does).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

CUSTOM_RE = re.compile(r"^%([A-Za-z0-9_\-]+?)(?:\.\d+)? = \(?[a-z0-9]+\[([0-9,]*)\]")
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
HOST_PREFIX = "bench."


@dataclasses.dataclass
class DeviceOp:
    name: str
    start_ns: float
    dur_ns: float
    kernel: Optional[str]       # launching function of a Pallas kernel
    shape: Optional[Tuple[int, ...]]   # the kernel op's (first) output


@dataclasses.dataclass
class Summary:
    """A traced window, reduced."""
    window_s: float
    busy_s: float                        # mean over the chips
    n_devices: int
    ops: List[DeviceOp]                  # every Pallas kernel op
    op_time_s: Dict[str, float]          # by op name, summed
    kernel_time_s: Dict[str, float]      # by kernel name, summed
    gaps: List[Tuple[str, float]]        # (host annotation, s): the 50
    #                                      longest, longest first
    host_time_s: Dict[str, float]        # bench.* annotation totals

    def kernel_ops(self, names) -> List[DeviceOp]:
        return [o for o in self.ops if o.kernel in names]

    def breakdown(self, k: int = 10) -> dict:
        by: Dict[str, float] = {}
        for name, sec in self.op_time_s.items():
            by[short_name(name)] = by.get(short_name(name), 0.0) + sec
        top = sorted(by.items(), key=lambda kv: -kv[1])[:k]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in self.gaps[:k]]}


def find_xplane(path: str) -> str:
    if path.endswith(".xplane.pb"):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def short_name(op_name: str) -> str:
    """An op's instruction name without ``%`` and ``.<n>``, with its
    custom-call target where it has one: ``run [tpu_custom_call]``."""
    m = re.match(r"^%([A-Za-z0-9_\-]+?)(?:\.\d+)? = ", op_name)
    base = m.group(1) if m else op_name[:64]
    t = re.search(r'custom_call_target="([^"]+)"', op_name)
    return f"{base} [{t.group(1)}]" if t else base


def _kernel_and_shape(ev) -> Tuple[Optional[str], Optional[tuple]]:
    if PALLAS_TARGET not in ev.name:
        return None, None
    m = CUSTOM_RE.match(ev.name)
    if m is None:
        return None, None
    return m.group(1), tuple(int(x) for x in m.group(2).split(",") if x)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _device_planes(pd):
    return [p for p in pd.planes
            if re.fullmatch(r"/device:TPU:\d+", p.name)]


def _ops_line(plane):
    lines = list(plane.lines)
    for ln in lines:
        if ln.name == "XLA Ops":
            return ln
    return None


def reduce_file(path: str) -> Summary:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(path))
    kernel_ops: List[DeviceOp] = []
    op_time: Dict[str, float] = {}
    parsed: Dict[str, Tuple[Optional[str], Optional[tuple]]] = {}
    busy_total = 0.0
    unions = []
    t0, t1 = float("inf"), float("-inf")
    for p in _device_planes(pd):
        ln = _ops_line(p)
        if ln is None:
            continue
        ivs = []
        for ev in ln.events:
            name, start, dur = ev.name, ev.start_ns, ev.duration_ns
            if name not in parsed:
                parsed[name] = _kernel_and_shape(ev)
            k, shape = parsed[name]
            if k is not None:
                kernel_ops.append(DeviceOp(name, start, dur, k, shape))
            op_time[name] = op_time.get(name, 0.0) + dur * 1e-9
            ivs.append((start, start + dur))
        u = _union(ivs)
        unions.append(u)
        busy_total += sum(b - a for a, b in u)
        if u:
            t0, t1 = min(t0, u[0][0]), max(t1, u[-1][1])
    host = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for ev in ln.events:
                if ev.name.startswith(HOST_PREFIX):
                    a = ev.start_ns
                    host.append((a, a + ev.duration_ns,
                                 ev.name[len(HOST_PREFIX):]))
                    t0, t1 = min(t0, a), max(t1, a + ev.duration_ns)
    # the window: what the trace covers, on its own clock
    if t1 < t0:
        t0 = t1 = 0.0
    window_ns = max(t1 - t0, 1.0)
    gaps: List[Tuple[str, float]] = []
    if unions:
        edges = [t0] + [x for iv in unions[0] for x in iv] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(_host_at(host, a, b), (b - a) * 1e-9) for a, b in gaps[:50]]
    kern: Dict[str, float] = {}
    for o in kernel_ops:
        kern[o.kernel] = kern.get(o.kernel, 0.0) + o.dur_ns * 1e-9
    host_time: Dict[str, float] = {}
    for a, b, n in host:
        host_time[n] = host_time.get(n, 0.0) + (b - a) * 1e-9
    n_dev = max(len(unions), 1)
    return Summary(window_ns * 1e-9, busy_total * 1e-9 / n_dev, len(unions),
                   kernel_ops, op_time, kern, named, host_time)


def _host_at(host, a: float, b: float) -> str:
    """The innermost annotation covering most of [a, b]."""
    best, best_len = "host", None
    for s, e, name in host:
        cover = min(e, b) - max(s, a)
        if cover * 2 < (b - a):
            continue
        if best_len is None or e - s < best_len:
            best, best_len = name, e - s
    return best
