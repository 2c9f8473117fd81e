"""Pallas TPU kernels for elementwise modular arithmetic — the NMU analog.

Kernels use ONLY u32 ops (16-bit limb composition + Montgomery REDC,
kernels/common.py), so they lower to the TPU VPU. Blocks are (8, block_n)
tiles of 8 limb rows; each row's modulus and -q^{-1} ride along as an
(8, 1) column broadcast across the lanes.

Semantics contract (see ref.py): operand `b` is pre-converted to Montgomery
form by the ops.py wrapper, so `mont_mul32(a, b_mont) == a*b mod q` exactly.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import addmod32, mont_mul32, pallas_call

U32 = jnp.uint32


ROWS = 8      # sublanes per block: (8, block_n) tiles are TPU-native


def _pad(arrs, rows: int, n: int, block_n: int):
    """Zero-pad rows up to a multiple of ROWS and the last axis up to a
    multiple of block_n.

    `grid = (l, n // block_n)` silently DROPS the tail block when
    n % block_n != 0 (the trailing coefficients come back as zeros) —
    regression-tested in tests/test_kernels.py. Zero entries are inert
    for every kernel here (mont_mul32(0, b) == 0), so pad + slice is
    exact."""
    pad_r = (-rows) % ROWS
    pad_c = (-n) % block_n
    if pad_r == 0 and pad_c == 0:
        return arrs
    return [jnp.pad(a, ((0, pad_r), (0, pad_c))) for a in arrs]


def _pad_scalars(q, qinv_neg):
    """Per-row (rows_pad, 1) moduli; padded rows reuse the last modulus
    so their (discarded) arithmetic stays well defined."""
    pad_r = (-q.shape[0]) % ROWS
    return [jnp.pad(x, (0, pad_r), mode="edge")[:, None]
            for x in (q, qinv_neg)]


def _modmul_kernel(a_ref, b_ref, q_ref, qinv_ref, o_ref):
    o_ref[...] = mont_mul32(a_ref[...], b_ref[...], q_ref[...],
                            qinv_ref[...])


def _mulacc_kernel(a_ref, b_ref, c_ref, q_ref, qinv_ref, o_ref):
    q = q_ref[...]
    prod = mont_mul32(a_ref[...], b_ref[...], q, qinv_ref[...])
    o_ref[...] = addmod32(prod, c_ref[...], q)


def _rowwise(kernel, data, q, qinv_neg, block_n: int, interpret: bool):
    """Launch ``kernel`` over (ROWS, block_n) tiles of (L, N) operands
    with per-row moduli riding along as (ROWS, 1) columns."""
    l, n = data[0].shape
    block_n = min(block_n, n)
    data = _pad(data, l, n, block_n)
    scal = _pad_scalars(q, qinv_neg)
    l_pad, n_pad = data[0].shape
    tile = pl.BlockSpec((ROWS, block_n), lambda i, j: (i, j))
    col = pl.BlockSpec((ROWS, 1), lambda i, j: (i, 0))
    return pallas_call(
        kernel,
        grid=(l_pad // ROWS, n_pad // block_n),
        in_specs=[tile] * len(data) + [col, col],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((l_pad, n_pad), U32),
        interpret=interpret,
    )(*data, *scal)[:l, :n]


def modmul_pallas(a, b_mont, q, qinv_neg, *, block_n: int = 512,
                  interpret: bool = True):
    """a, b_mont: (L, N) u32; q, qinv_neg: (L,) u32. Returns (a*b) mod q."""
    return _rowwise(_modmul_kernel, [a, b_mont], q, qinv_neg, block_n,
                    interpret)


def mulacc_pallas(a, b_mont, c, q, qinv_neg, *, block_n: int = 512,
                  interpret: bool = True):
    """(a*b + c) mod q — fused NMU multiply-accumulate."""
    return _rowwise(_mulacc_kernel, [a, b_mont, c], q, qinv_neg, block_n,
                    interpret)
