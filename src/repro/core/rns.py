"""RNS basis tooling: fast base conversion (BConv), ModDown, Rescale.

BConv (paper §II-A eq.(1), §IV-D) is the all-to-all primitive of FHE:

    BConv_{Q->P}(a)_i = [ sum_j [a_j * qhat_j^{-1}]_{q_j} * [qhat_j]_{p_i} ]_{p_i}

Every output limb depends on every input limb. In FHEmem, limbs live in
different banks and this runs on the partial-chain inter-bank network; here
limbs live on different devices along the `model` mesh axis and the same
dependency becomes an all_gather/psum_scatter (repro/fhe_dist). This module
is the exact single-device reference; it operates on *coefficient-domain*
polys as the paper prescribes (an iNTT precedes BConv).

This is the "fast" (HPS-style) conversion: the result may be off by a small
multiple of Q — the standard full-RNS CKKS approximation the paper also
inherits from [24].
"""
from __future__ import annotations

from typing import List, NamedTuple, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import modarith as ma


class BConvTables(NamedTuple):
    """Host-precomputed constants for one (src basis -> dst basis) pair."""
    qhat_inv: jnp.ndarray   # (S,)  [qhat_j^{-1}]_{q_j}
    w: jnp.ndarray          # (S, D) [qhat_j]_{p_i}
    src_q: jnp.ndarray      # (S,)
    dst_q: jnp.ndarray      # (D,)


def make_bconv_tables(src_primes: Sequence[int],
                      dst_primes: Sequence[int]) -> BConvTables:
    src = [int(p) for p in src_primes]
    dst = [int(p) for p in dst_primes]
    big_q = 1
    for p in src:
        big_q *= p
    qhat = [big_q // p for p in src]
    qhat_inv = [pow(h % p, -1, p) for h, p in zip(qhat, src)]
    w = np.array([[h % pi for pi in dst] for h in qhat], dtype=np.uint64)
    return BConvTables(
        qhat_inv=jnp.asarray(np.array(qhat_inv, dtype=np.uint64)),
        w=jnp.asarray(w),
        src_q=jnp.asarray(np.array(src, dtype=np.uint64)),
        dst_q=jnp.asarray(np.array(dst, dtype=np.uint64)),
    )


def bconv(a: jnp.ndarray, t: BConvTables) -> jnp.ndarray:
    """Fast base conversion. a: (..., S, N) coeff domain -> (..., D, N).

    Reference schedule: reduce each partial product immediately (the
    kernels use lazy accumulation — see repro/kernels/bconv.py).
    """
    v = ma.mulmod(a, t.qhat_inv[:, None], t.src_q[:, None])   # (..., S, N)
    s = v.shape[-2]
    acc = None
    for j in range(s):
        # (D, 1) * (..., 1, N) -> (..., D, N), reduced mod dst
        term = ma.mulmod(v[..., j:j + 1, :], t.w[j][:, None], t.dst_q[:, None])
        acc = term if acc is None else acc + term   # sum of reduced < S*2^31
    return acc % t.dst_q[:, None]


def bconv_matmul(a: jnp.ndarray, t: BConvTables) -> jnp.ndarray:
    """BConv as an explicit (S,N)x(S,D) contraction — the form the Pallas
    kernel and the MXU mapping use. Exact: lazy u64 accumulation with
    periodic folding every 8 partial products (8 * 2^62-ish < 2^64 needs
    products < 2^61; v<2^31, w<2^30 in our parameter regime)."""
    v = ma.mulmod(a, t.qhat_inv[:, None], t.src_q[:, None])
    s = v.shape[-2]
    acc = jnp.zeros(a.shape[:-2] + (t.w.shape[1],) + a.shape[-1:], dtype=jnp.uint64)
    run = None
    for j in range(s):
        prod = v[..., j:j + 1, :] * t.w[j][:, None]            # < 2^61, unreduced
        run = prod if run is None else run + prod
        if (j + 1) % 4 == 0 or j == s - 1:                     # fold every 4
            acc = (acc + run % t.dst_q[:, None]) % t.dst_q[:, None]
            run = None
    return acc


# ---------------------------------------------------------------------------
# ModDown / Rescale helpers (coeff-domain cores; NTT wrapping in ops.py)
# ---------------------------------------------------------------------------

def mod_down_coeff(a_q: jnp.ndarray, a_p_converted: jnp.ndarray,
                   p_inv_mod_q: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    """(a_q - BConv_{P->Q}(a_p)) * P^{-1} mod q. All (..., L, N) coeff/NTT."""
    diff = ma.submod(a_q, a_p_converted % q[:, None], q[:, None])
    return ma.mulmod(diff, p_inv_mod_q[:, None], q[:, None])


def exact_div_by_last_coeff(a: jnp.ndarray, q_last_inv: jnp.ndarray,
                            q: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Rescale core: given a (..., L, N) with last limb already broadcast-
    subtracted, multiply by q_last^{-1} mod q_i. Returns (..., L-1, N)."""
    return ma.mulmod(a, q_last_inv[:, None], q[:, None])


def crt_lift_centered(limbs: np.ndarray, primes: Sequence[int]) -> np.ndarray:
    """Exact CRT reconstruction to centered Python ints (host, object array).

    limbs: (L, N) uint64. Returns (N,) object array in (-Q/2, Q/2].
    The exact reference for the mixed-radix lift that decode runs
    (`mixed_radix_centred` + `horner`); tests compare against it.
    """
    primes = [int(p) for p in primes]
    big_q = 1
    for p in primes:
        big_q *= p
    acc = np.zeros(limbs.shape[-1], dtype=object)
    for j, p in enumerate(primes):
        qhat = big_q // p
        corr = (qhat * pow(qhat % p, -1, p))
        acc = (acc + limbs[j].astype(object) * corr) % big_q
    return np.where(acc > big_q // 2, acc - big_q, acc)


# ---------------------------------------------------------------------------
# Mixed-radix (Garner) lift: decode's CRT on the device
# ---------------------------------------------------------------------------
# x in [0, Q) with residues r_i = x mod q_i has the mixed-radix digits
#   x = d_0 + q_0 (d_1 + q_1 (d_2 + ...)),   0 <= d_i < q_i,
# found by Garner's recurrence: once rows < j are peeled off, row j is d_j,
# and every row i > j becomes (r_i - d_j) * q_j^{-1} mod q_i. Each step is
# word arithmetic over all coefficients, so it runs batched on the device.
# A prefix of the chain has the leading block of the chain's constants.

class LiftTables(NamedTuple):
    """Constants of the mixed-radix lift over primes q_0..q_{L-1}."""
    q: np.ndarray           # (L,)   the primes
    one_shoup: np.ndarray   # (L,)   floor(2^32 / q_i)
    inv: np.ndarray         # (L, L) [j, i] = q_j^{-1} mod q_i for j < i, else 0
    inv_shoup: np.ndarray   # (L, L) floor(inv[j, i] * 2^32 / q_i)
    half: np.ndarray        # (L,)   mixed-radix digits of floor(Q / 2)

    def prefix(self, n: int) -> "LiftTables":
        """The tables of the first ``n`` primes."""
        primes = [int(p) for p in self.q[:n]]
        return LiftTables(self.q[:n], self.one_shoup[:n],
                          self.inv[:n, :n], self.inv_shoup[:n, :n],
                          _half_digits(primes))


def mixed_radix(x: int, primes: Sequence[int]) -> List[int]:
    """Mixed-radix digits of a Python int 0 <= x < prod(primes)."""
    out = []
    for p in primes:
        x, d = divmod(x, int(p))
        out.append(d)
    return out


def _half_digits(primes: Sequence[int]) -> np.ndarray:
    big_q = 1
    for p in primes:
        big_q *= p
    return np.array(mixed_radix(big_q // 2, primes), dtype=np.uint64)


def lift_tables(primes: Sequence[int]) -> LiftTables:
    primes = [int(p) for p in primes]
    n = len(primes)
    inv = np.zeros((n, n), dtype=np.uint64)
    inv_shoup = np.zeros((n, n), dtype=np.uint64)
    for j, qj in enumerate(primes):
        for i in range(j + 1, n):
            w = pow(qj % primes[i], -1, primes[i])
            inv[j, i], inv_shoup[j, i] = w, (w << 32) // primes[i]
    return LiftTables(np.array(primes, dtype=np.uint64),
                      np.array([(1 << 32) // p for p in primes],
                               dtype=np.uint64),
                      inv, inv_shoup, _half_digits(primes))


def _mulmod_shoup(a, w, w_shoup, q):
    """a * w mod q for a < 2^32 and w < q < 2^31, w_shoup =
    floor(w 2^32 / q): the quotient estimate undershoots by at most one,
    so one conditional subtraction and no division."""
    r = a * w - ((a * w_shoup) >> 32) * q
    return jnp.where(r >= q, r - q, r)


@jax.jit
def mixed_radix_centred(a: jnp.ndarray, t: LiftTables
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Coefficient residues -> mixed-radix digits of the centred value.

    a: (..., L, N) uint64 residues of x in [0, Q); t: `lift_tables` of
    the L primes. Returns (digits of |c|, (..., L, N) uint32; c < 0,
    (..., N) bool) for c the centred x in (-Q/2, Q/2], as
    `crt_lift_centered` gives it.
    """
    n_limbs = a.shape[-2]
    if n_limbs > 30:
        raise ValueError(f"{n_limbs} limbs: the row weights are int32")
    q = t.q[:, None]
    rows = jnp.arange(n_limbs)[:, None]

    def garner_step(j, x):
        # rows > j: (x_i - d_j) * q_j^{-1} mod q_i; rows <= j are digits
        d = jax.lax.dynamic_index_in_dim(x, j, axis=-2)     # (..., 1, N)
        d_mod = _mulmod_shoup(d, 1, t.one_shoup[:, None], q)
        y = _mulmod_shoup(x + (q - d_mod), t.inv[j][:, None],
                          t.inv_shoup[j][:, None], q)
        return jnp.where(rows > j, y, x)
    x = jax.lax.fori_loop(0, n_limbs - 1, garner_step, a)
    # row i weighs 2^i, so a sum over rows is decided by its top row:
    # x > floor(Q/2) where the top digit that differs is the greater
    weight = jnp.left_shift(1, jnp.arange(n_limbs, dtype=jnp.int32))[:, None]
    above = (x > t.half[:, None]).astype(jnp.int32)
    below = (x < t.half[:, None]).astype(jnp.int32)
    neg = jnp.sum((above - below) * weight, axis=-2) > 0
    # those take |c| = Q - x, digit by digit: 0 below x's lowest nonzero
    # digit k, q_k - d_k at k, q_i - 1 - d_i above (the borrow of k)
    nonzero = jnp.sum((x != 0).astype(jnp.int32) * weight, axis=-2)
    borrow = (nonzero[..., None, :] & (weight - 1)) != 0
    flipped = jnp.where(borrow, q - 1 - x, jnp.where(x == 0, x, q - x))
    out = jnp.where(neg[..., None, :], flipped, x)
    return out.astype(jnp.uint32), neg


WIDE = 2.0 ** 53


def horner(digits: np.ndarray, neg: np.ndarray,
           primes: Sequence[int]) -> Tuple[np.ndarray, int]:
    """Mixed-radix digits (..., L, N) and signs (..., N) -> float64
    values, and how many have |c| >= 2^53.

    Horner from the top digit, v = v * q_i + d_i. Below 2^53 every
    partial value is an integer under 2^53, so the result is exactly
    ``float(int(c))``; above, each step rounds once (and the count is
    exact: rounding is monotone and 2^53 is representable). Top rows that
    are zero throughout add exact zeros, so the recurrence starts below
    them: where q_0 q_1 > 2^53, as in the paper's chains, values under
    2^53 have two digits."""
    top = len(primes) - 1
    while top > 0 and not digits[..., top, :].max():
        top -= 1
    v = digits[..., top, :].astype(np.float64)
    for i in range(top - 1, -1, -1):
        v *= float(primes[i])
        v += digits[..., i, :]
    return np.where(neg, -v, v), int(np.count_nonzero(v >= WIDE))
