"""CKKS bootstrapping: ModRaise -> CoefToSlot -> EvalMod -> SlotToCoef.

This is the paper's flagship deep workload (§V-B "Bootstrapping", and the
CoefToSlot pipeline of Fig. 10). Full-slot (Han-Ki style) flow:

1. ModRaise: reinterpret a level-0 ciphertext at level L; the hidden message
   becomes t = m + q0*I with small integer polynomial I (sparse secret).
2. CoefToSlot: homomorphic linear transform moving coefficients into slots,
   packed z_j = (c_j + i*c_{j+N/2})/Delta — one ciphertext.
3. EvalMod: approximate t -> t mod q0 via the scaled sine
   (q0/2pi) sin(2pi t/q0), evaluated with Chebyshev interpolation on the
   real and imaginary parts separately.
4. SlotToCoef: inverse linear transform.

Two forms of the linear transforms live here:

* `dense_dft`: the dense (N/2)x(N/2) matrices, derived numerically from the
  canonical embedding. `Bootstrapper` runs them on single ciphertexts
  through core/ops; it is the plain reference for small N in tests (at
  logN=16 the matrix alone would take ~68 GB).
* `BootstrapPlan`: what the serving engine runs (`CkksEngine.bootstrap`).
  The special FFT's log2(N/2) butterfly layers (HEAAN's ``fftSpecial``,
  5^j slot order) are grouped into a few levels (Chen-Chillotti-Song,
  EUROCRYPT 2019): a group of k layers is one sparse matrix with about
  2^(k+1) - 1 diagonals, evaluated baby-step giant-step. The bit reversal
  the FFT needs is left out: CoeffToSlot puts the coefficients in
  bit-reversed slot order, EvalMod is slot-wise, and SlotToCoeff reads
  them in that order. EvalMod is the cosine of Han-Ki (CT-RSA 2020):
  a Chebyshev interpolant of cos(2pi (x - 1/4) / 2^r) followed by r
  double angles, which gives sin(2pi x).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax.numpy as jnp

from repro.core import linalg, ops as hops
from repro.core.ciphertext import Ciphertext, KeySwitchKey
from repro.core.context import CkksContext
from repro.core.encoder import CkksEncoder
from repro.core.params import CkksParams, chebyshev_ladder_depth


def dense_dft(encoder: CkksEncoder) -> Tuple[np.ndarray, np.ndarray]:
    """The dense CoeffToSlot and SlotToCoeff matrices (s x s, s = N/2):
    z = A_cts v and v = A_stc z, where v are the slots and z_k = c_k + i
    c_{k+N/2} packs the real coefficients. Both are C-linear: the
    conjugate-slot parts of the general real-linear form vanish."""
    n = encoder.n
    s = n // 2
    # canonical embedding matrix V (s x n): v_j = sum_k c_k zeta^{k e_j}
    V = np.exp(1j * np.pi * np.outer(encoder.slot_exp, np.arange(n)) / n)
    W = np.vstack([V, np.conj(V)])           # (n, n)
    Winv = np.linalg.inv(W)                  # c = Winv @ [v; conj v]
    P = Winv[:, :s]
    a_cts = P[:s] + 1j * P[s:]
    a_stc = V[:, :s]                         # v = V_L (c_low + i c_high)
    return a_cts, a_stc


# ---------------------------------------------------------------------------
# the factored transforms and EvalMod, as the engine runs them
# ---------------------------------------------------------------------------

def _butterfly(s: int, length: int, inverse: bool) -> Dict[int, np.ndarray]:
    """One butterfly stage of the special FFT (block ``length``) as
    generalized diagonals over s slots: diag_d[p] = M[p, (p + d) % s].
    Forward: (u, v) -> (u + w v, u - w v); inverse: its inverse."""
    lenh, lenq = length // 2, 4 * length
    w = np.exp(2j * np.pi * np.array([pow(5, j, lenq) for j in range(lenh)])
               / lenq)
    p = np.arange(s)
    up = p % length < lenh
    w = w[p % length % lenh]
    if inverse:
        w = np.conj(w)
        parts = [(0, np.where(up, 0.5, -w / 2)), (lenh, np.where(up, 0.5, 0)),
                 (s - lenh, np.where(up, 0, w / 2))]
    else:
        parts = [(0, np.where(up, 1, -w)), (lenh, np.where(up, w, 0)),
                 (s - lenh, np.where(up, 0, 1))]
    out: Dict[int, np.ndarray] = {}
    for d, v in parts:              # +lenh and -lenh meet when length = s
        out[d] = out.get(d, 0) + v
    return out


def _compose(a: Dict[int, np.ndarray], b: Dict[int, np.ndarray],
             s: int) -> Dict[int, np.ndarray]:
    """Diagonals of A @ B (B applied first)."""
    out: Dict[int, np.ndarray] = {}
    for da, va in a.items():
        for db, vb in b.items():
            d = (da + db) % s
            out[d] = out.get(d, 0) + va * np.roll(vb, -da)
    return out


@dataclasses.dataclass
class DftLevel:
    """One level of a factored transform: the product of consecutive
    butterfly stages, whose diagonals all sit at multiples of ``gap``.
    ``diags`` maps m (signed) to the diagonal at offset ``gap * m``;
    BSGS with ``baby`` baby steps: m = baby * i + j, 0 <= j < baby."""
    gap: int
    baby: int
    diags: Dict[int, np.ndarray]

    @functools.cached_property
    def terms(self) -> Dict[int, List[Tuple[int, np.ndarray]]]:
        """Giant step i -> [(baby step j, diagonal pre-rotated by the
        giant step)]: out = sum_i rot(sum_j pt_ij * rot(x, gap j),
        gap baby i), with rot(v, k)[p] = v[p + k]."""
        out: Dict[int, List[Tuple[int, np.ndarray]]] = {}
        for m in sorted(self.diags):
            i, j = divmod(m, self.baby)
            out.setdefault(i, []).append(
                (j, np.roll(self.diags[m], self.gap * self.baby * i)))
        return out

    @property
    def rotation_steps(self) -> List[int]:
        s = len(next(iter(self.diags.values())))
        steps = set()
        for i, terms in self.terms.items():
            steps.update(self.gap * j % s for j, _ in terms)
            steps.add(self.gap * self.baby * i % s)
        steps.discard(0)
        return sorted(steps)


def _dft_levels(log_slots: int, n_levels: int,
                inverse: bool) -> List[DftLevel]:
    """The special FFT's stages grouped into ``n_levels`` levels, in the
    order they apply. Groups are as even as possible, the smaller ones
    over the lowest stages; CoeffToSlot (inverse) runs the groups from
    the top stage down, SlotToCoeff (forward) from the bottom up, so both
    use the same rotations."""
    s = 1 << log_slots
    n_levels = min(n_levels, log_slots)
    q, r = divmod(log_slots, n_levels)
    sizes = [q] * (n_levels - r) + [q + 1] * r       # bottom stage first
    groups, t = [], 1
    for k in sizes:
        groups.append(list(range(t, t + k)))
        t += k
    if inverse:
        groups = [g[::-1] for g in groups[::-1]]
    out = []
    for g in groups:
        mat = {0: np.ones(s, dtype=np.complex128)}
        for t in g:
            mat = _compose(_butterfly(s, 1 << t, inverse), mat, s)
        gap = 1 << (min(g) - 1)
        period = s // gap
        diags = {}
        for d, v in mat.items():
            if np.abs(v).max() > 1e-12:
                m = (d // gap) % period
                diags[m - period if m >= period // 2 and period > 1
                      else m] = v
        baby = 1 << max(0, math.ceil(math.log2(math.sqrt(len(diags)))))
        out.append(DftLevel(gap, baby, diags))
    return out


class BootstrapPlan:
    """The numbers a parameter set's bootstrap runs on: the factored
    CoeffToSlot and SlotToCoeff levels, EvalMod's Chebyshev coefficients
    and ladder depth, and the Galois elements its keys are for. Pure
    numpy, built once per parameter set (`bootstrap_plan`)."""

    def __init__(self, params: CkksParams):
        assert params.has_bootstrap, "parameter set has no bootstrap settings"
        self.params = params
        log_slots = params.log_n - 1
        self.slots = 1 << log_slots
        self.cts = _dft_levels(log_slots, params.bts_dft_levels, True)
        self.stc = _dft_levels(log_slots, params.bts_dft_levels, False)
        self.cheb = linalg.chebyshev_coeffs(self._cosine,
                                            params.bts_cheb_degree)
        self.ladder_depth = chebyshev_ladder_depth(params.bts_cheb_degree)

    def _cosine(self, y: np.ndarray) -> np.ndarray:
        """The function the series interpolates, of y = x / (K+1) on
        [-1, 1]: r double angles of it give cos(2 pi (x - 1/4)) =
        sin(2 pi x)."""
        p = self.params
        return np.cos(2 * np.pi * ((p.bts_k_range + 1) * y - 0.25)
                      / 2 ** p.bts_double_angle)

    def eval_mod_plain(self, x: np.ndarray) -> np.ndarray:
        """What EvalMod computes of x = t / q0, in float64: the cosine's
        Chebyshev series at y = x / (K+1), then the double angles;
        about sin(2 pi x)."""
        p = self.params
        c = np.polynomial.chebyshev.chebval(x / (p.bts_k_range + 1),
                                            self.cheb)
        for _ in range(p.bts_double_angle):
            c = 2 * c * c - 1
        return c

    @property
    def eval_mod_bound(self) -> float:
        """Bound on |eval_mod_plain(x) - sin(2 pi x)| for |x| <= K+1:
        the interpolant's error (at most twice the tail of the series,
        read from an interpolant of twice the degree) grown at most 4x
        by each double angle (|d(2c^2 - 1)/dc| <= 4)."""
        p = self.params
        wide = linalg.chebyshev_coeffs(self._cosine,
                                       2 * p.bts_cheb_degree + 1)
        tail = np.abs(wide[p.bts_cheb_degree + 1:]).sum()
        return 4.0 ** p.bts_double_angle * 2 * tail + 1e-12

    @property
    def rotation_steps(self) -> List[int]:
        steps = set()
        for lvl in self.cts + self.stc:
            steps.update(lvl.rotation_steps)
        return sorted(steps)

    def galois_elements(self) -> List[int]:
        """Every Galois key a bootstrap switches to: the DFT rotations
        and the conjugation of the real/imaginary split."""
        from repro.core.ntt import galois_element
        n = self.params.n
        return sorted({galois_element(st, n) for st in self.rotation_steps}
                      | {2 * n - 1})

    def plaintext_limbs(self, raise_level: int) -> int:
        """Limbs of the plaintexts a bootstrap from ``raise_level`` pins:
        every DFT diagonal at its level, and the two +-i monomials of the
        real/imaginary split and its undoing."""
        p = self.params
        lvl, limbs = raise_level, 0
        for level in self.cts:
            limbs += len(level.diags) * (lvl + 1)
            lvl -= 1
        limbs += lvl + 1                       # -i at EvalMod's input
        lvl -= p.bts_evalmod_levels
        limbs += lvl + 1                       # +i at its output
        for level in self.stc:
            limbs += len(level.diags) * (lvl + 1)
            lvl -= 1
        return limbs


@functools.lru_cache(maxsize=None)
def bootstrap_plan(params: CkksParams) -> BootstrapPlan:
    return BootstrapPlan(params)


@dataclasses.dataclass
class BootstrapConfig:
    eval_mod_degree: int = 31     # Chebyshev degree for sin
    k_range: float = 12.0         # |t/q0| bound (depends on secret hamming wt)
    cts_level_cost: int = 1
    stc_level_cost: int = 1


class Bootstrapper:

    def __init__(self, ctx: CkksContext, encoder: CkksEncoder,
                 encryptor, sk, config: Optional[BootstrapConfig] = None):
        self.ctx = ctx
        self.encoder = encoder
        self.config = config or BootstrapConfig()
        self.A_cts, self.A_stc = dense_dft(encoder)
        self.diags_A_cts = linalg.matrix_diagonals(self.A_cts)
        self.diags_A_stc = linalg.matrix_diagonals(self.A_stc)
        # keys
        elts = set()
        for dg in (self.diags_A_cts, self.diags_A_stc):
            elts.update(linalg.matvec_keys_needed(ctx, dg))
        elts.add(ctx.conj_element)
        self.gks: Dict[int, KeySwitchKey] = encryptor.galois_keygen(
            sk, sorted(elts))
        self.rk: KeySwitchKey = encryptor.relin_keygen(sk)
        # Chebyshev coefficients of sin(2*pi*K*y) on y in [-1, 1]
        kk = self.config.k_range
        self.cheb = linalg.chebyshev_coeffs(
            lambda y: np.sin(2 * np.pi * kk * y), self.config.eval_mod_degree)

    # -- stages --------------------------------------------------------------

    def mod_raise(self, ct: Ciphertext, target_level: int) -> Ciphertext:
        """Level-0 ciphertext -> target_level; message becomes m + q0*I."""
        assert ct.level == 0
        ctx = self.ctx
        q0 = ctx.primes[0]
        coeff = np.asarray(ctx.intt(ct.data, [0]))[:, 0]        # (2, N)
        centered = coeff.astype(np.int64)
        centered = np.where(centered > q0 // 2, centered - q0, centered)
        idx = ctx.q_idx(target_level)
        primes = np.array([ctx.primes[i] for i in idx], dtype=np.int64)
        limbs = (centered[:, None, :] % primes[None, :, None]).astype(np.uint64)
        data = ctx.ntt(jnp.asarray(limbs), idx)
        return Ciphertext(data, target_level, ct.scale)

    def coef_to_slot(self, ct: Ciphertext) -> Ciphertext:
        return linalg.matvec_bsgs(self.ctx, ct, self.diags_A_cts, self.gks,
                                  self.encoder)

    def slot_to_coef(self, ct: Ciphertext) -> Ciphertext:
        return linalg.matvec_bsgs(self.ctx, ct, self.diags_A_stc, self.gks,
                                  self.encoder)

    def eval_mod(self, ct: Ciphertext, q0_over_scale: float) -> Ciphertext:
        """Input slots: t/Delta (t = m + q0 I). Output slots: ~ m/Delta."""
        ctx, enc = self.ctx, self.encoder
        kk = self.config.k_range
        # y = t / (q0 * K) in [-1, 1]
        y = linalg.mul_const(ctx, enc, ct, 1.0 / (q0_over_scale * kk))
        g = linalg.poly_eval_chebyshev(ctx, y, self.cheb, self.rk, enc)
        # m/Delta ~= (q0/Delta) * sin(2 pi t / q0) / (2 pi)
        return linalg.mul_const(ctx, enc, g, q0_over_scale / (2 * np.pi))

    # -- full pipeline ---------------------------------------------------------

    def bootstrap(self, ct: Ciphertext, target_level: int) -> Ciphertext:
        """level-0 -> refreshed ciphertext at a usable level."""
        ctx = self.ctx
        q0 = ctx.primes[0]
        raised = self.mod_raise(ct, target_level)
        z = self.coef_to_slot(raised)
        # split real/imag
        z_conj = hops.conjugate(ctx, z, self.gks[ctx.conj_element])
        z_conj.scale = z.scale
        re = hops.hadd(ctx, z, z_conj)
        re = linalg.mul_const(ctx, self.encoder, re, 0.5)
        im = hops.hsub(ctx, z, z_conj)
        im = linalg.mul_const(ctx, self.encoder, im, -0.5j)
        q0_over_scale = q0 / ct.scale
        re_m = self.eval_mod(re, q0_over_scale)
        im_m = self.eval_mod(im, q0_over_scale)
        im_i = linalg.mul_const(ctx, self.encoder, im_m, 1j)
        re_m = linalg.adjust_to(ctx, self.encoder, re_m, im_i.level, im_i.scale)
        z2 = hops.hadd(ctx, re_m, im_i)
        out = self.slot_to_coef(z2)
        return out
