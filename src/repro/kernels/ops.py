"""jit'd public wrappers around the Pallas kernels.

Handles Montgomery-form conversion of the constant operands (host/jit-side
u64 math via core.modarith — cheap and exact), dtype casts u64<->u32, and
interpret-mode selection (interpret=True on CPU; compiled on TPU).
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import modarith as ma
from repro.kernels import bconv as bconv_k
from repro.kernels import modmul as modmul_k
from repro.kernels.common import default_interpret, record_dispatch
from repro.kernels.ntt import FourStepKernelTables, ntt_four_step_pallas
from repro.kernels.ref import FourStepTables


@lru_cache(maxsize=256)
def _mont_consts(primes: tuple):
    """Per-prime-basis constants (q as u64/u32, -q^-1 mod 2^32, R mod q).

    Cached on the prime tuple: the host-side modular inverses and the
    four host->device transfers would otherwise run on EVERY wrapper
    call — eager per-call work outside the jit boundary, the same bug
    class the fused keyswitch pipeline fixed (tests guard this stays
    cached)."""
    q64 = jnp.asarray(np.array(primes, dtype=np.uint64))
    q32 = jnp.asarray(np.array(primes, dtype=np.uint32))
    qinv = jnp.asarray(np.array(
        [(-pow(int(p), -1, 1 << 32)) % (1 << 32) for p in primes],
        dtype=np.uint32))
    # R mod q: plain mulmod(b, rm) == b * 2^32 mod q (Montgomery form)
    rm = jnp.asarray(np.array([(1 << 32) % int(p) for p in primes],
                              dtype=np.uint64))
    return q64, q32, qinv, rm


def _key(primes: Sequence[int]) -> tuple:
    return tuple(int(p) for p in primes)


@partial(jax.jit, static_argnames=("interpret",))
def _modmul_impl(a, b, q64, q32, qinv, rm, interpret=True):
    b_mont = ma.mulmod(b, rm[:, None], q64[:, None]).astype(jnp.uint32)
    return modmul_k.modmul_pallas(a.astype(jnp.uint32), b_mont, q32, qinv,
                                  interpret=interpret).astype(jnp.uint64)


def modmul(a, b, primes: Sequence[int], interpret=None):
    """(a*b) mod q per limb. a, b: (L, N) u64; primes: python ints."""
    q64, q32, qinv, rm = _mont_consts(_key(primes))
    itp = default_interpret() if interpret is None else interpret
    record_dispatch(interpret=itp)
    return _modmul_impl(a, b, q64, q32, qinv, rm, interpret=itp)


@partial(jax.jit, static_argnames=("interpret",))
def _mulacc_impl(a, b, c, q64, q32, qinv, rm, interpret=True):
    b_mont = ma.mulmod(b, rm[:, None], q64[:, None]).astype(jnp.uint32)
    return modmul_k.mulacc_pallas(a.astype(jnp.uint32), b_mont,
                                  c.astype(jnp.uint32), q32, qinv,
                                  interpret=interpret).astype(jnp.uint64)


def mulacc(a, b, c, primes: Sequence[int], interpret=None):
    """(a*b + c) mod q per limb."""
    q64, q32, qinv, rm = _mont_consts(_key(primes))
    itp = default_interpret() if interpret is None else interpret
    record_dispatch(interpret=itp)
    return _mulacc_impl(a, b, c, q64, q32, qinv, rm, interpret=itp)


@partial(jax.jit, static_argnames=("lazy", "interpret"))
def _bconv_impl(v, wt, p64, p32, pinv, rm, lazy=False, interpret=True):
    # w -> Montgomery form w.r.t. each dst prime INSIDE the jit: the
    # conversion fuses into the compiled program instead of paying an
    # eager host-side dispatch (and a retrace) on every call.
    w_mont = ma.mulmod(wt % p64[:, None], rm[:, None],
                       p64[:, None]).astype(jnp.uint32)
    return bconv_k.bconv_pallas(v.astype(jnp.uint32), w_mont, p32, pinv,
                                lazy=lazy,
                                interpret=interpret).astype(jnp.uint64)


def bconv(v, w, dst_primes: Sequence[int], lazy: bool = False,
          interpret=None):
    """out[d] = sum_j v[j]*w[j,d] mod p_d. v: (S,N) u64; w: (S,D) u64."""
    p64, p32, pinv, rm64 = _mont_consts(_key(dst_primes))
    itp = default_interpret() if interpret is None else interpret
    record_dispatch(interpret=itp)
    return _bconv_impl(v, w.T, p64, p32, pinv, rm64, lazy=lazy,
                       interpret=itp)


class NttKernel:
    """Four-step NTT kernel bound to one modulus (tables cached)."""

    def __init__(self, q: int, psi: int, log_n: int, log_r: int):
        self.tabs = FourStepTables(q, psi, log_n, log_r)
        self.kt = FourStepKernelTables(self.tabs)

    def __call__(self, a, interpret=None, **blocks):
        itp = default_interpret() if interpret is None else interpret
        record_dispatch(2, interpret=itp)          # column kernel + fused row kernel
        return ntt_four_step_pallas(a.astype(jnp.uint32), self.kt,
                                    interpret=itp,
                                    **blocks).astype(jnp.uint64)
