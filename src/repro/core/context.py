"""CkksContext: the precompute hub for a CKKS parameter set.

Owns: moduli chain, NTT tables for the full Q∪P basis, reduction constants,
cached BConv tables per (src,dst) basis pair, cached Galois permutations.
Everything host-precomputed once; runtime ops are pure jnp on these arrays.
"""
from __future__ import annotations

import copy
import functools
from typing import Dict, List, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import modarith as ma
from repro.core import ntt as nttm
from repro.core import rns
from repro.core.params import CkksParams


class CkksContext:
    """``transform``, when given, runs every NTT/iNTT in place of the
    library transform: an object with ``ntt(a, idx)`` and ``intt(a,
    idx)`` over global limb indices, bit-identical to core/ntt.py (the
    engine's kernel route hands in the Pallas limb NTT).

    Tables built lazily (limb slices, BConv tables, Galois permutations)
    are built at compile time even inside a jit trace, so they stay
    concrete, cacheable arrays."""

    def __init__(self, params: CkksParams, transform=None):
        self.params = params
        self.transform = transform
        self.log_n = params.log_n
        self.n = params.n
        self.moduli = params.moduli                       # Q then P
        self.n_q = params.n_q_moduli
        self.n_p = params.n_special
        self.primes: List[int] = [m.value for m in self.moduli]
        self.q_primes = self.primes[: self.n_q]
        self.p_primes = self.primes[self.n_q:]

        # NTT tables over the whole basis; limb slices are cheap views.
        self.tables = nttm.NttTables(self.moduli, self.log_n)
        self.q_all = self.tables.q                        # (n_q+n_p,)

        # reduction constants per limb
        self.barrett_mu = jnp.asarray(
            np.array([ma.barrett_mu(p) for p in self.primes], dtype=np.uint64))
        self.mont_qinv_neg = jnp.asarray(
            np.array([ma.mont_qinv_neg(p) for p in self.primes], dtype=np.uint64))
        self.mont_r2 = jnp.asarray(
            np.array([ma.mont_r2(p) for p in self.primes], dtype=np.uint64))

        # P^{-1} mod q_j (ModDown constant)
        big_p = 1
        for p in self.p_primes:
            big_p *= p
        self.big_p = big_p
        self.p_inv_mod_q = jnp.asarray(np.array(
            [pow(big_p % q, -1, q) for q in self.q_primes], dtype=np.uint64))

        # q_last^{-1} mod q_i for every rescale level: rescale from level l
        # drops prime index l; constants[l][i] = q_l^{-1} mod q_i for i<l
        self._qlast_inv: List[jnp.ndarray] = []
        for l in range(self.n_q):
            if l == 0:
                self._qlast_inv.append(jnp.zeros((0,), dtype=jnp.uint64))
            else:
                ql = self.q_primes[l]
                self._qlast_inv.append(jnp.asarray(np.array(
                    [pow(ql % qi, -1, qi) for qi in self.q_primes[:l]],
                    dtype=np.uint64)))

        self._bconv_cache: Dict[Tuple[Tuple[int, ...], Tuple[int, ...]],
                                rns.BConvTables] = {}
        self._eval_perm_cache: Dict[int, jnp.ndarray] = {}
        self._limb_tables_cache: Dict[Tuple[int, ...], nttm.NttTables] = {}
        self._lift_cache: Dict[int, rns.LiftTables] = {}

    # -- basis helpers ------------------------------------------------------

    def q_idx(self, level: int) -> List[int]:
        """Global limb indices of the active Q basis at `level`."""
        return list(range(level + 1))

    def p_idx(self) -> List[int]:
        return list(range(self.n_q, self.n_q + self.n_p))

    def limb_tables(self, idx: Sequence[int]) -> nttm.NttTables:
        key = tuple(idx)
        if key not in self._limb_tables_cache:
            with jax.ensure_compile_time_eval():
                self._limb_tables_cache[key] = self.tables.slice_limbs(
                    list(key))
        return self._limb_tables_cache[key]

    def bconv_tables(self, src_idx: Sequence[int],
                     dst_idx: Sequence[int]) -> rns.BConvTables:
        key = (tuple(src_idx), tuple(dst_idx))
        if key not in self._bconv_cache:
            with jax.ensure_compile_time_eval():
                self._bconv_cache[key] = rns.make_bconv_tables(
                    [self.primes[i] for i in key[0]],
                    [self.primes[i] for i in key[1]])
        return self._bconv_cache[key]

    # -- NTT wrappers over global limb indices ------------------------------

    def ntt(self, a: jnp.ndarray, idx: Sequence[int]) -> jnp.ndarray:
        if self.transform is not None:
            return self.transform.ntt(a, idx)
        return nttm.ntt(a, self.limb_tables(idx))

    def intt(self, a: jnp.ndarray, idx: Sequence[int]) -> jnp.ndarray:
        if self.transform is not None:
            return self.transform.intt(a, idx)
        return nttm.intt(a, self.limb_tables(idx))

    def with_transform(self, transform) -> "CkksContext":
        """This context with its NTTs run by ``transform``; every table
        and cache is shared with the original."""
        view = copy.copy(self)
        view.transform = transform
        return view

    # -- Galois -------------------------------------------------------------

    def eval_perm(self, galois_elt: int) -> jnp.ndarray:
        """NTT-domain automorphism permutation (same for every limb)."""
        if galois_elt not in self._eval_perm_cache:
            perm = nttm.eval_perm(galois_elt, self.primes[0],
                                  self.tables.psi[0], self.log_n)
            with jax.ensure_compile_time_eval():
                self._eval_perm_cache[galois_elt] = jnp.asarray(perm)
        return self._eval_perm_cache[galois_elt]

    def rotation_element(self, step: int) -> int:
        return nttm.galois_element(step, self.n)

    @property
    def conj_element(self) -> int:
        return 2 * self.n - 1

    def lift_tables(self, level: int) -> rns.LiftTables:
        """Decode's mixed-radix lift constants at ``level``
        (`rns.mixed_radix_centred`), on the device: the leading block of
        the chain's tables and the digits of floor(Q_level / 2)."""
        if level not in self._lift_cache:
            self._lift_cache[level] = rns.LiftTables(
                *map(jnp.asarray, self._chain_lift.prefix(level + 1)))
        return self._lift_cache[level]

    @functools.cached_property
    def _chain_lift(self) -> rns.LiftTables:
        return rns.lift_tables(self.q_primes)

    # -- misc ---------------------------------------------------------------

    def qlast_inv(self, level: int) -> jnp.ndarray:
        return self._qlast_inv[level]

    @functools.cached_property
    def q_products(self) -> List[int]:
        """prod(q_0..q_l) per level (python ints, for scale bookkeeping)."""
        out, acc = [], 1
        for p in self.q_primes:
            acc *= p
            out.append(acc)
        return out
