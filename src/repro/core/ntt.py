"""Negacyclic NTT over RNS limbs, vectorized in JAX.

Layout notes (the FHEmem analogy, DESIGN.md §2): the iterative Harvey/CT
NTT's stages split naturally into *large-stride* stages (pairs live in
different rows of an (R, C) tile view — FHEmem's "vertical inter-mat"
phase), *mid-stride* stages (pairs in the same row, different tiles —
"horizontal inter-mat"), and *small-stride* stages (pairs inside one tile —
"intra-mat"). The Pallas kernels in repro/kernels/ntt.py exploit exactly
this split; this module is the canonical reference implementation and the
library path.

Conventions:
* forward NTT: natural-order input -> bit-reversed-order evaluation domain
  (evaluations of the polynomial at odd powers of psi, psi = 2N-th root);
* all elementwise ciphertext algebra happens in that bit-reversed domain;
* automorphisms in the evaluation domain are pure permutations
  (``eval_perm``), computed from the exponent map — no sign fixups needed.

Data: ``(..., L, N)`` uint64; per-limb constants ``(L,)``.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import modarith as ma
from repro.core.params import Modulus, find_2nth_root


def bit_reverse(i: int, bits: int) -> int:
    return int(bin(i + (1 << bits))[3:][::-1], 2)


def bit_reverse_vector(n: int) -> np.ndarray:
    bits = n.bit_length() - 1
    return np.array([bit_reverse(i, bits) for i in range(n)], dtype=np.int64)


# ---------------------------------------------------------------------------
# table construction (host side)
# ---------------------------------------------------------------------------

class NttTables:
    """Per-modulus-set twiddle tables for ring degree N.

    root_powers[l, i]     = psi_l^{brv(i, logN)}
    inv_root_powers[l, i] = psi_l^{-brv(i, logN)}
    """

    def __init__(self, moduli: Sequence[Modulus], log_n: int):
        self.log_n = log_n
        self.n = 1 << log_n
        self.moduli = tuple(moduli)
        two_n = 2 * self.n
        q_list, rp_list, irp_list, ninv_list, psi_list = [], [], [], [], []
        brv = bit_reverse_vector(self.n)
        for mod in moduli:
            p = mod.value
            psi = find_2nth_root(p, two_n)
            psi_inv = pow(psi, -1, p)
            # psi^i for i in 0..N-1 (then permute by brv) — O(N) host work
            pw = np.empty(self.n, dtype=np.uint64)
            ipw = np.empty(self.n, dtype=np.uint64)
            x = 1
            y = 1
            for i in range(self.n):
                pw[i] = x
                ipw[i] = y
                x = x * psi % p
                y = y * psi_inv % p
            rp_list.append(pw[brv])
            irp_list.append(ipw[brv])
            q_list.append(p)
            ninv_list.append(pow(self.n, -1, p))
            psi_list.append(psi)
        self.q = jnp.asarray(np.array(q_list, dtype=np.uint64))
        self.root_powers = jnp.asarray(np.stack(rp_list))
        self.inv_root_powers = jnp.asarray(np.stack(irp_list))
        self.n_inv = jnp.asarray(np.array(ninv_list, dtype=np.uint64))
        self.psi = tuple(psi_list)

    def slice_limbs(self, idx: Sequence[int]) -> "NttTables":
        """View of a subset of limbs (no recomputation)."""
        out = object.__new__(NttTables)
        out.log_n = self.log_n
        out.n = self.n
        idx = list(idx)
        out.moduli = tuple(self.moduli[i] for i in idx)
        ii = jnp.asarray(np.array(idx, dtype=np.int64))
        out.q = self.q[ii]
        out.root_powers = self.root_powers[ii]
        out.inv_root_powers = self.inv_root_powers[ii]
        out.n_inv = self.n_inv[ii]
        out.psi = tuple(self.psi[i] for i in idx)
        return out


# ---------------------------------------------------------------------------
# forward / inverse (vectorized over leading dims and limbs)
# ---------------------------------------------------------------------------

def ntt_forward(a: jnp.ndarray, root_powers: jnp.ndarray,
                q: jnp.ndarray) -> jnp.ndarray:
    """Cooley-Tukey DIT, natural -> bitrev. a: (..., L, N)."""
    n = a.shape[-1]
    lead = a.shape[:-1]  # (..., L)
    m = 1
    while m < n:
        t = n // (2 * m)
        a = a.reshape(*lead, m, 2 * t)
        w = root_powers[..., m:2 * m]            # (L, m)
        u = a[..., :t]
        v = ma.mulmod(a[..., t:], w[..., :, None], q[..., None, None])
        a = jnp.concatenate(
            [ma.addmod(u, v, q[..., None, None]),
             ma.submod(u, v, q[..., None, None])], axis=-1)
        m *= 2
    return a.reshape(*lead, n)


def ntt_inverse(a: jnp.ndarray, inv_root_powers: jnp.ndarray,
                q: jnp.ndarray, n_inv: jnp.ndarray) -> jnp.ndarray:
    """Gentleman-Sande DIF, bitrev -> natural (exact inverse of forward)."""
    n = a.shape[-1]
    lead = a.shape[:-1]
    m = n // 2
    while m >= 1:
        t = n // (2 * m)
        a = a.reshape(*lead, m, 2 * t)
        w = inv_root_powers[..., m:2 * m]        # (L, m)
        u = a[..., :t]
        v = a[..., t:]
        s = ma.addmod(u, v, q[..., None, None])
        d = ma.mulmod(ma.submod(u, v, q[..., None, None]),
                      w[..., :, None], q[..., None, None])
        a = jnp.concatenate([s, d], axis=-1)
        m //= 2
    a = a.reshape(*lead, n)
    return ma.mulmod(a, n_inv[..., None], q[..., None])


_ntt_forward_jit = jax.jit(ntt_forward)
_ntt_inverse_jit = jax.jit(ntt_inverse)


def ntt(a: jnp.ndarray, tables: NttTables) -> jnp.ndarray:
    return _ntt_forward_jit(a, tables.root_powers, tables.q)


def intt(a: jnp.ndarray, tables: NttTables) -> jnp.ndarray:
    return _ntt_inverse_jit(a, tables.inv_root_powers, tables.q, tables.n_inv)


# ---------------------------------------------------------------------------
# reference O(N^2) oracle (tests only)
# ---------------------------------------------------------------------------

def negacyclic_convolve_ref(a: np.ndarray, b: np.ndarray, p: int) -> np.ndarray:
    """Schoolbook product in Z_p[X]/(X^N+1); a, b: (N,) ints."""
    n = len(a)
    out = np.zeros(n, dtype=object)
    aa = a.astype(object)
    bb = b.astype(object)
    for i in range(n):
        # contribution of b[i]: shift a by i with sign wrap
        part = np.concatenate([-aa[n - i:], aa[: n - i]]) if i else aa
        out = (out + part * bb[i]) % p
    return out.astype(np.uint64)


# ---------------------------------------------------------------------------
# Galois automorphisms
# ---------------------------------------------------------------------------

def galois_element(step: int, n: int) -> int:
    """Galois element for Rotate(step) on N/2 slots: 5^step mod 2N.

    Negative steps rotate the other way; step=None conventionally means
    conjugation (element 2N-1), handled by callers.
    """
    two_n = 2 * n
    return pow(5, step % (n // 2), two_n)


CONJ_ELEMENT_OFFSET = -1  # conjugation is element 2N-1


def coeff_perm(galois_elt: int, n: int) -> Tuple[np.ndarray, np.ndarray]:
    """Coefficient-domain automorphism sigma_k: a_i -> (+/-) a'_{ik mod N}.

    Returns (src_index, negate) such that
    ``out[j] = negate[j] ? q - a[src[j]] : a[src[j]]`` (gather form).
    """
    k = galois_elt
    i = np.arange(n, dtype=np.int64)
    e = (i * k) % (2 * n)
    dest = e % n
    neg_at_dest = (e >= n)
    src = np.empty(n, dtype=np.int64)
    neg = np.empty(n, dtype=bool)
    src[dest] = i
    neg[dest] = neg_at_dest
    return src, neg


def _ntt_forward_host(a: np.ndarray, rp: np.ndarray, p: int) -> np.ndarray:
    """ntt_forward for one limb in numpy (host), same stages and layout:
    table construction must not run op by op on an accelerator."""
    n = len(a)
    q = np.uint64(p)
    m = 1
    while m < n:
        t = n // (2 * m)
        a = a.reshape(m, 2 * t)
        u = a[:, :t]
        v = (a[:, t:] * rp[m:2 * m, None]) % q
        a = np.concatenate([(u + v) % q, (u + q - v) % q], axis=1)
        m *= 2
    return a.reshape(n)


@functools.lru_cache(maxsize=None)
def _exponent_order_cached(p: int, psi: int, log_n: int) -> tuple:
    """The exponent e_i such that forward-NTT output slot i holds a(psi^{e_i})."""
    n = 1 << log_n
    # NTT of X: slot i = psi^{e_i}
    x_poly = np.zeros(n, dtype=np.uint64)
    x_poly[1] = 1
    brv = bit_reverse_vector(n)
    pw = np.empty(n, dtype=np.uint64)
    x = 1
    for i in range(n):
        pw[i] = x
        x = x * psi % p
    vals = _ntt_forward_host(x_poly, pw[brv], p)
    val_to_exp = {pow(psi, e, p): e for e in range(1, 2 * n, 2)}
    return tuple(val_to_exp[int(v)] for v in vals)


def eval_perm(galois_elt: int, p: int, psi: int, log_n: int) -> np.ndarray:
    """Evaluation(NTT)-domain automorphism permutation.

    out_slot[i] = in_slot[perm[i]]  implements  sigma_k  in the NTT domain —
    this is the beyond-paper "NTT-domain rotation" optimization (the paper
    permutes in coefficient domain with its interleaved mat layout §IV-E;
    on TPU a static gather in the evaluation domain avoids the iNTT/NTT
    round-trip entirely).
    """
    n = 1 << log_n
    exps = _exponent_order_cached(p, psi, log_n)
    pos = {e: i for i, e in enumerate(exps)}
    k = galois_elt
    return np.array([pos[(e * k) % (2 * n)] for e in exps], dtype=np.int64)
