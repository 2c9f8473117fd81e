"""u32-only modular arithmetic primitives shared by the Pallas TPU kernels.

TPU vector lanes are 32-bit: there is no u64 datapath. A 32x32->64 product
is composed from four 16x16->32 partial products — the same
"compose wide multiply from narrow hardware" move as FHEmem's digit-serial
NMU (DESIGN.md §2). All helpers below use ONLY u32 ops so they lower to
TPU Pallas; in interpret mode they run exactly on CPU too.

Moduli are < 2^31 (word32 RNS mode). Montgomery radix R = 2^32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

U32 = jnp.uint32
MASK16 = 0xFFFF  # python int: avoids captured-constant arrays in Pallas kernels

# ---------------------------------------------------------------------------
# dispatch accounting
# ---------------------------------------------------------------------------
# Every host-side kernel launch (one `pl.pallas_call` invocation, or one
# jitted library dispatch standing in for a kernel on the staged route)
# records itself here. This is the currency of the fig14 fused-vs-staged
# comparison: HE-PIM/MemFHE-style dispatch-granularity overhead is about
# how many times the host touches the device per keyswitch, so we count
# launches at the Python wrapper layer — code already captured inside an
# enclosing jit trace records at trace time only, which is exactly the
# steady-state launch count. Launches in Pallas interpret mode are also
# counted apart: on a TPU none may happen (chip_smoke.py checks it), off a
# TPU they are the only kind.

_dispatch_count = 0
_interpret_count = 0


def default_interpret() -> bool:
    """Kernels compile on a TPU and run in interpret mode elsewhere."""
    return jax.default_backend() != "tpu"


def record_dispatch(n: int = 1, interpret: bool = False) -> None:
    global _dispatch_count, _interpret_count
    _dispatch_count += n
    if interpret:
        _interpret_count += n


def dispatch_count() -> int:
    return _dispatch_count


def interpret_dispatch_count() -> int:
    return _interpret_count


def reset_dispatch_count() -> None:
    global _dispatch_count, _interpret_count
    _dispatch_count = 0
    _interpret_count = 0


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call`` traced with 64-bit types off.

    repro.core switches jax_enable_x64 on for its u64 residues; under it
    the python ints of index maps and kernel bodies lower as i64, which
    the TPU compiler refuses. Every kernel here is u32/i32 only."""
    call = pl.pallas_call(kernel, **kwargs)

    def run(*args):
        with jax.enable_x64(False):
            return call(*args)
    return run


def mul32_wide(a, b):
    """Full 64-bit product of u32 inputs as (hi32, lo32), u32-only ops."""
    a_lo = a & MASK16
    a_hi = a >> 16
    b_lo = b & MASK16
    b_hi = b >> 16
    ll = a_lo * b_lo
    lh = a_lo * b_hi
    hl = a_hi * b_lo
    hh = a_hi * b_hi
    mid = (ll >> 16) + (lh & MASK16) + (hl & MASK16)     # < 3*2^16
    lo = (mid << 16) | (ll & MASK16)
    hi = hh + (lh >> 16) + (hl >> 16) + (mid >> 16)
    return hi, lo


def mont_mul32(a, b, q, qinv_neg):
    """Montgomery product a*b*R^-1 mod q (R=2^32, q<2^31 odd).

    Inputs reduced (< q). qinv_neg = -q^{-1} mod 2^32. Result < q.
    """
    hi, lo = mul32_wide(a, b)
    m = lo * qinv_neg                      # mod 2^32 (native u32 wrap)
    mq_hi, mq_lo = mul32_wide(m, q)
    # lo + mq_lo == 0 mod 2^32 by construction; carry unless both are 0
    carry = (lo != 0).astype(U32)
    t = hi + mq_hi + carry
    return jnp.where(t >= q, t - q, t)


def addmod32(a, b, q):
    r = a + b                              # < 2^32 since a,b < q < 2^31
    return jnp.where(r >= q, r - q, r)


def submod32(a, b, q):
    return jnp.where(a >= b, a - b, a + (q - b))


def to_mont32(a, q, qinv_neg, r2):
    """a -> a*R mod q given r2 = R^2 mod q."""
    return mont_mul32(a, r2, q, qinv_neg)


def from_mont32(a, q, qinv_neg):
    """a*R^-1 mod q (multiply by 1 in Montgomery space)."""
    hi = jnp.zeros_like(a)
    m = a * qinv_neg
    mq_hi, _ = mul32_wide(m, q)
    carry = (a != 0).astype(U32)
    t = hi + mq_hi + carry
    return jnp.where(t >= q, t - q, t)
