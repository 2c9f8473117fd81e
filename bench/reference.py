"""Plain reference of the served programs, in numpy, independent of the
program under test.

Each program is a file ``bench/programs/<name>.py`` with ``consts(**args)``
(the names of its plaintext constants) and ``run(*inputs, c, **args)``,
written against ``Vec``: a slot vector whose ``rotate(k)`` is a roll
(``out[i] = in[i + k]``), and whose every product and sum is rounded to a
chosen precision (float64 for the reference, lower for the control).

Server-side state is made here by the same published rule the serving
backend states for itself (0.25 x standard normal per slot from a crc32 of
'<program>/const/<name>' or '<program>/input/<position>'); nothing is read
from the program.
"""
from __future__ import annotations

import importlib.util
import os
import zlib
from typing import Dict, List, Optional, Sequence

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
AMPLITUDE = 0.25


def _load_program(name: str):
    path = os.path.join(HERE, "programs", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_program_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Vec:
    """A (rows, slots) block of slot vectors under one rounding rule."""

    __slots__ = ("v", "dtype")

    def __init__(self, v, dtype=None):
        self.dtype = dtype
        self.v = self._round(np.asarray(v, dtype=np.float64))

    def _round(self, a):
        if self.dtype is None:
            return a
        return a.astype(self.dtype).astype(np.float64)

    def _wrap(self, a):
        return Vec(a, self.dtype)

    @staticmethod
    def _val(o):
        return o.v if isinstance(o, Vec) else o

    def __mul__(self, o):
        return self._wrap(self.v * self._val(o))

    __rmul__ = __mul__

    def __add__(self, o):
        return self._wrap(self.v + self._val(o))

    __radd__ = __add__

    def rotate(self, k: int):
        return self._wrap(np.roll(self.v, -k, axis=-1))


def _stable_normal(slots: int, *parts: str) -> np.ndarray:
    seed = zlib.crc32("/".join(parts).encode()) & 0xFFFFFFFF
    return AMPLITUDE * np.random.default_rng(seed).standard_normal(slots)


class Reference:
    """One served program of a configuration, evaluated in plain numpy."""

    def __init__(self, workload: str, spec: dict, slots: int):
        self.workload = workload
        self.mod = _load_program(spec["reference"])
        self.args = dict(spec.get("reference_args", {}))
        self.n_inputs = int(spec["inputs"])
        self.slots = slots
        self.consts = {name: _stable_normal(slots, workload, "const", name)
                       for name in self.mod.consts(**self.args)}
        self.aux = [_stable_normal(slots, workload, "input", str(i))
                    for i in range(1, self.n_inputs)]

    def evaluate(self, rows: np.ndarray, dtype=None) -> np.ndarray:
        """Program output for (B, slots) packed payload rows; every
        intermediate rounded to ``dtype`` (None: float64)."""
        def vec(a):
            return Vec(a, dtype)
        c = {k: vec(v).v for k, v in self.consts.items()}
        inputs = [vec(rows)] + [vec(np.broadcast_to(a, rows.shape))
                                for a in self.aux]
        return self.mod.run(*inputs, c, **self.args).v


def pack_rows(slot_groups: Sequence[Sequence[tuple]], n_rows: int,
              slots: int, payloads: Dict[int, np.ndarray]) -> np.ndarray:
    """Each request owns a contiguous slot range of its ciphertext row, in
    the order the batcher placed it: ``slot_groups`` holds, per row, the
    (request id, slots) pairs."""
    x = np.zeros((n_rows, slots))
    for row, group in enumerate(slot_groups):
        off = 0
        for rid, n in group:
            x[row, off:off + n] = payloads[rid][:n]
            off += n
    return x


def request_errors(slot_groups, outputs: np.ndarray,
                   ref: np.ndarray) -> List[tuple]:
    """(request id, widest |served - reference| over its own slots)."""
    out = []
    for row, group in enumerate(slot_groups):
        off = 0
        for rid, n in group:
            gap = np.abs(outputs[row, off:off + n] - ref[row, off:off + n])
            out.append((rid, float(gap.max())))
            off += n
    return out


def precision(name: Optional[str]):
    """A rounding dtype by name: None/'float64', 'float32', 'bfloat16',
    'float8_e4m3fn', ..."""
    if name in (None, "float64"):
        return None
    if name == "float32":
        return np.float32
    import ml_dtypes
    return getattr(ml_dtypes, name)
