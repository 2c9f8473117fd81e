"""Per-limb negacyclic NTT for Pallas TPU kernels, in the (R, 128) tile layout.

A limb of N coefficients lives in a kernel as an ``(R, C)`` u32 tile
with ``C = min(N, 128)`` lanes and ``R = N // C`` rows (flat index
``i = r*C + c``), which is the native (8, 128) vreg layout of the TPU.
The stages are exactly those of core/ntt.py (Harvey CT forward,
natural -> bit-reversed; Gentleman-Sande inverse without the n^{-1}
scale), so the results are bit-identical to the library path.

Every stage is a butterfly between ``i`` and ``i ^ t``. The partner is
fetched with ``pltpu.roll``: along the sublane (row) axis when
``t >= C`` and along the lane axis when ``t < C``. Neither needs a
reshape, so no stage depends on sub-tile relayouts.

Twiddles: a stage's twiddle ``w = rp[m + i // (2t)]`` depends on the row
only when ``t >= C``; when ``t < C`` it factors exactly into a per-row
and a per-lane value, because ``m``, the row part and the lane part of
``m + i // (2t)`` occupy disjoint bits and bit reversal keeps them
disjoint: ``w(r, c) = rp[m + A(r)] * rp[m + B(c)] / rp[m]``. So the
tables are a per-row ``(R, S)`` factor and a per-lane ``(S, C)`` factor
per limb (S = log2 N stages), both in Montgomery form — about 1/8 of the
limb's own size at N = 2^16, small enough to stay resident per limb.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (addmod32, default_interpret, mont_mul32,
                                  pallas_call, record_dispatch, submod32)

U32 = jnp.uint32
LANES = 128


def tile_shape(n: int) -> Tuple[int, int]:
    """(R, C) tile of one limb of n coefficients."""
    c = min(n, LANES)
    return n // c, c


def _stages(n: int, inverse: bool) -> List[Tuple[int, int]]:
    """(m, t) per stage in execution order (core/ntt.py's loops)."""
    out = []
    m = n // 2 if inverse else 1
    while 1 <= m < n:
        out.append((m, n // (2 * m)))
        m = m // 2 if inverse else m * 2
    return out


def _mont(x: np.ndarray, p: int) -> np.ndarray:
    """x -> x·2^32 mod p as u32 (x < 2^31: the u64 product cannot wrap)."""
    rm = np.uint64((1 << 32) % p)
    return ((np.asarray(x, dtype=np.uint64) * rm) % np.uint64(p)
            ).astype(np.uint32)


def stage_factors(powers: np.ndarray, p: int, inverse: bool
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-row and per-lane Montgomery twiddle factors of one limb, from
    its bit-reversed root-power table (N,) (root_powers for the forward,
    inv_root_powers for the inverse transform).

    Returns ``row (R, 128)`` — column s holds stage s's per-row factor —
    and ``lane (S, 1, C)``, stage s's per-lane factor (1 above C lanes)."""
    powers = np.asarray(powers, dtype=np.uint64)
    n = len(powers)
    r_n, c_n = tile_shape(n)
    rows = np.arange(r_n, dtype=np.int64)
    lanes = np.arange(c_n, dtype=np.int64)
    stages = _stages(n, inverse)
    assert len(stages) <= LANES
    f = np.ones((r_n, LANES), dtype=np.uint64)
    g = np.ones((len(stages), 1, c_n), dtype=np.uint64)
    for s, (m, t) in enumerate(stages):
        if t >= c_n:
            f[:, s] = powers[m + rows // (2 * t // c_n)]
        else:
            f[:, s] = powers[m + rows * (c_n // (2 * t))]
            inv_m = pow(int(powers[m]), -1, p)
            g[s, 0] = (powers[m + lanes // (2 * t)] * np.uint64(inv_m)
                       ) % np.uint64(p)
    return _mont(f, p), _mont(g, p)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LimbNttTables:
    """Montgomery twiddle factors of a prime basis, stacked per limb."""
    fwd_row: jnp.ndarray          # (L, R, 128)
    fwd_lane: jnp.ndarray         # (L, S, 1, C)
    inv_row: jnp.ndarray          # (L, R, 128)
    inv_lane: jnp.ndarray         # (L, S, 1, C)
    q32: jnp.ndarray              # (L,)
    qinv32: jnp.ndarray           # (L,)  -q^{-1} mod 2^32
    n_inv_m: jnp.ndarray          # (L,)  n^{-1} in Montgomery form


def qinv_neg32(p: int) -> int:
    return (-pow(int(p), -1, 1 << 32)) % (1 << 32)


def build_tables(primes, root_powers, inv_root_powers, n_inv
                 ) -> LimbNttTables:
    """Stack per-limb factors from core/ntt.py NttTables arrays."""
    rp = np.asarray(root_powers)
    irp = np.asarray(inv_root_powers)
    fr, fl, ir, il = [], [], [], []
    for j, p in enumerate(primes):
        a, b = stage_factors(rp[j], p, inverse=False)
        c, d = stage_factors(irp[j], p, inverse=True)
        fr.append(a), fl.append(b), ir.append(c), il.append(d)
    ninv = np.asarray(n_inv, dtype=np.uint64)
    return LimbNttTables(
        fwd_row=jnp.asarray(np.stack(fr)), fwd_lane=jnp.asarray(np.stack(fl)),
        inv_row=jnp.asarray(np.stack(ir)), inv_lane=jnp.asarray(np.stack(il)),
        q32=jnp.asarray(np.array(primes, dtype=np.uint32)),
        qinv32=jnp.asarray(np.array([qinv_neg32(p) for p in primes],
                                    dtype=np.uint32)),
        n_inv_m=jnp.asarray(np.array(
            [_mont(ninv[j:j + 1], p)[0] for j, p in enumerate(primes)],
            dtype=np.uint32)))


def context_tables(ctx) -> LimbNttTables:
    """The tables of a CkksContext's whole basis."""
    t = ctx.tables
    return build_tables(ctx.primes, t.root_powers, t.inv_root_powers,
                        t.n_inv)


# ---------------------------------------------------------------------------
# in-kernel stage loops on an (R, C) value
# ---------------------------------------------------------------------------
# Each loop runs one butterfly body over a dynamic stage index, so the
# kernel's code size (and its compile time) does not grow with log N.

def _butterfly_stages(x, row_ref, lane_ref, q, qi, *, first: int,
                      count: int, axis: int, inverse: bool):
    """Stages first..first+count-1, all pairing along ``axis``. Forward
    strides halve from size/2; inverse strides double from 1."""
    if count == 0:
        return x
    size = x.shape[axis]
    idx = lax.broadcasted_iota(jnp.int32, x.shape, axis)
    rows_tab = row_ref[...]

    def body(k, x):
        s = first + k
        stride = (jnp.left_shift(1, k) if inverse
                  else jnp.right_shift(size, k + 1)).astype(jnp.int32)
        up = pltpu.roll(x, size - stride, axis)      # x[i + stride]
        dn = pltpu.roll(x, stride, axis)             # x[i - stride]
        top = (idx & stride) == 0
        u = jnp.where(top, x, dn)
        v = jnp.where(top, up, x)
        # stage s's row factor is column s of the row table: rotate it
        # to lane 0 and broadcast it across the lanes
        w_row = pltpu.roll(rows_tab, (LANES - s) % LANES, 1)[:, :1]
        if inverse:
            y = mont_mul32(submod32(u, v, q), w_row, q, qi)
        else:
            y = mont_mul32(v, w_row, q, qi)
        if axis == 1:
            y = mont_mul32(y, lane_ref[s], q, qi)
        if inverse:
            return jnp.where(top, addmod32(u, v, q), y)
        return jnp.where(top, addmod32(u, y, q), submod32(u, y, q))

    return lax.fori_loop(0, count, body, x)


def _split(x) -> Tuple[int, int]:
    """(row stages, lane stages) of an (R, C) tile."""
    r_n, c_n = x.shape
    return r_n.bit_length() - 1, c_n.bit_length() - 1


def ntt_fwd(x, row_ref, lane_ref, q, qi):
    """Forward CT NTT of an (R, C) limb tile (natural -> bit-reversed):
    the row stages (t >= C) come first, then the lane stages."""
    n_row, n_lane = _split(x)
    x = _butterfly_stages(x, row_ref, lane_ref, q, qi, first=0,
                          count=n_row, axis=0, inverse=False)
    return _butterfly_stages(x, row_ref, lane_ref, q, qi, first=n_row,
                             count=n_lane, axis=1, inverse=False)


def ntt_inv(x, row_ref, lane_ref, q, qi):
    """Inverse GS NTT of an (R, C) limb tile, without the n^{-1} scale
    (callers fold it into their own final multiply)."""
    n_row, n_lane = _split(x)
    x = _butterfly_stages(x, row_ref, lane_ref, q, qi, first=0,
                          count=n_lane, axis=1, inverse=True)
    return _butterfly_stages(x, row_ref, lane_ref, q, qi, first=n_lane,
                             count=n_row, axis=0, inverse=True)


# ---------------------------------------------------------------------------
# standalone limb-wise NTT kernel (the library ntt/intt on the kernel route)
# ---------------------------------------------------------------------------

def _ntt_kernel(ids_ref, q_ref, qi_ref, sc_ref, x_ref, row_ref, lane_ref,
                o_ref, *, inverse: bool):
    g = ids_ref[pl.program_id(1)]
    q = q_ref[g]
    qi = qi_ref[g]
    if inverse:
        y = ntt_inv(x_ref[...], row_ref, lane_ref, q, qi)
        o_ref[...] = mont_mul32(y, sc_ref[g], q, qi)
    else:
        o_ref[...] = ntt_fwd(x_ref[...], row_ref, lane_ref, q, qi)


def ntt_pallas(x, tabs: LimbNttTables, limb_ids, *, inverse: bool,
               interpret: bool):
    """Limb-wise NTT of x (B, L, R, C) u32; limb j of x uses the tables'
    limb ``limb_ids[j]``. The inverse applies n^{-1}."""
    b, l, r_n, c_n = x.shape
    s_n = tabs.fwd_lane.shape[1]
    row_t, lane_t = ((tabs.inv_row, tabs.inv_lane) if inverse
                     else (tabs.fwd_row, tabs.fwd_lane))
    limb = (pl.squeezed, pl.squeezed, r_n, c_n)
    return pallas_call(
        functools.partial(_ntt_kernel, inverse=inverse),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, l),
            in_specs=[
                pl.BlockSpec(limb, lambda i, j, ids, *_: (i, j, 0, 0)),
                pl.BlockSpec((pl.squeezed, r_n, LANES),
                             lambda i, j, ids, *_: (ids[j], 0, 0)),
                pl.BlockSpec((pl.squeezed, s_n, 1, c_n),
                             lambda i, j, ids, *_: (ids[j], 0, 0, 0))],
            out_specs=pl.BlockSpec(limb, lambda i, j, ids, *_: (i, j, 0, 0))),
        out_shape=jax.ShapeDtypeStruct(x.shape, U32),
        interpret=interpret,
    )(limb_ids, tabs.q32, tabs.qinv32, tabs.n_inv_m,
      x, row_t, lane_t)


def to_tiles(a: jnp.ndarray) -> jnp.ndarray:
    """(..., L, N) u64 -> (B, L, R, C) u32 (B = product of leading dims)."""
    r_n, c_n = tile_shape(a.shape[-1])
    return a.astype(U32).reshape(-1, a.shape[-2], r_n, c_n)


def from_tiles(x: jnp.ndarray, shape) -> jnp.ndarray:
    return x.reshape(shape).astype(jnp.uint64)


def ntt_limbs(a: jnp.ndarray, tabs: LimbNttTables, limb_ids,
              inverse: bool, interpret: Optional[bool] = None
              ) -> jnp.ndarray:
    """core/ntt.ntt / intt on the kernel route: a (..., L, N) u64 in,
    same shape u64 out, bit-identical to the library transform."""
    itp = default_interpret() if interpret is None else interpret
    record_dispatch(interpret=itp)
    return _ntt_limbs(a, tabs, np.asarray(limb_ids, np.int32),
                      inverse=inverse, interpret=itp)


@functools.partial(jax.jit, static_argnames=("inverse", "interpret"))
def _ntt_limbs(a, tabs, limb_ids, *, inverse: bool, interpret: bool):
    out = ntt_pallas(to_tiles(a), tabs, limb_ids, inverse=inverse,
                     interpret=interpret)
    return from_tiles(out, a.shape)


class LimbNtt:
    """The kernel route's NTT for ``CkksContext.with_transform``: every
    ctx.ntt / ctx.intt runs as ``ntt_limbs`` over ``tables`` (concrete,
    or the traced argument of an enclosing jit)."""

    def __init__(self, tables: LimbNttTables):
        self.tables = tables

    def ntt(self, a: jnp.ndarray, idx) -> jnp.ndarray:
        return ntt_limbs(a, self.tables, idx, inverse=False)

    def intt(self, a: jnp.ndarray, idx) -> jnp.ndarray:
        return ntt_limbs(a, self.tables, idx, inverse=True)
