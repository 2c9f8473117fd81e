"""CKKS canonical-embedding encoder/decoder (SIMD slot packing).

Slots: v in C^{N/2}. Encode finds the real polynomial m(X) in R with
m(zeta^{5^j}) = v_j (and the conjugate constraint at zeta^{-5^j}), scaled by
`scale` and rounded; zeta = exp(i*pi/N) is a primitive 2N-th root of unity.

Implemented with the twist trick: for odd e = 2t+1,
    m(zeta^e) = sum_k (m_k zeta^k) e^{2*pi*i*t*k/N}
so evaluations at all odd exponents are one length-N DFT of the twisted
coefficients — O(N log N) via numpy FFT in float64 (host side; encoding is
I/O, not the accelerated path the paper optimizes).
"""
from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import rns
from repro.core.context import CkksContext
from repro.obs.hook import layer


class CkksEncoder:

    def __init__(self, ctx: CkksContext):
        self.ctx = ctx
        n = ctx.n
        self.n = n
        self.slots = n // 2
        two_n = 2 * n
        # slot j <-> odd exponent 5^j mod 2N; conjugate slot at 2N - 5^j
        e = 1
        slot_exp = np.empty(self.slots, dtype=np.int64)
        for j in range(self.slots):
            slot_exp[j] = e
            e = (e * 5) % two_n
        self.slot_exp = slot_exp
        self.slot_t = (slot_exp - 1) // 2            # position in odd-DFT order
        self.conj_t = ((two_n - slot_exp) - 1) // 2
        k = np.arange(n)
        self.zeta_pow = np.exp(1j * np.pi * k / n)   # zeta^k
        self.zeta_pow_inv = np.conj(self.zeta_pow)

    # -- float coefficient domain <-> slots ---------------------------------

    def embed_inverse(self, v: np.ndarray) -> np.ndarray:
        """Slots -> real coefficient vector (unscaled float64)."""
        assert v.shape[-1] == self.slots
        vals = np.zeros(v.shape[:-1] + (self.n,), dtype=np.complex128)
        vals[..., self.slot_t] = v
        vals[..., self.conj_t] = np.conj(v)
        twisted = np.fft.fft(vals, axis=-1) / self.n   # sum_t vals_t e^{-2pi i tk/N}
        m = twisted * self.zeta_pow_inv
        return np.real(m)

    def embed_forward(self, m: np.ndarray) -> np.ndarray:
        """Real coefficients -> slots (float64 -> complex128)."""
        twisted = m.astype(np.complex128) * self.zeta_pow
        vals = np.fft.ifft(twisted, axis=-1) * self.n  # sum_k twisted_k e^{+2pi i tk/N}
        return vals[..., self.slot_t]

    # -- RNS plaintexts ------------------------------------------------------

    def encode(self, v: Sequence[complex], scale: float,
               level: int) -> jnp.ndarray:
        """Complex slots -> RNS plaintext (level+1, N) in NTT domain."""
        v = np.asarray(v, dtype=np.complex128)
        if v.ndim == 0:
            v = np.full(self.slots, complex(v))
        if v.shape[-1] != self.slots:
            full = np.zeros(self.slots, dtype=np.complex128)
            full[: v.shape[-1]] = v
            v = full
        coeffs = np.round(self.embed_inverse(v) * scale).astype(np.int64)
        return self.to_rns_ntt(coeffs, level)

    def to_rns_ntt(self, coeffs: np.ndarray, level: int) -> jnp.ndarray:
        """Signed int64 coefficients -> (level+1, N) NTT-domain RNS limbs."""
        idx = self.ctx.q_idx(level)
        primes = np.array([self.ctx.primes[i] for i in idx], dtype=np.int64)
        limbs = (coeffs[None, :] % primes[:, None]).astype(np.uint64)
        return self.ctx.ntt(jnp.asarray(limbs), idx)

    def decode(self, pt_ntt: jnp.ndarray, scale: float,
               level: int) -> np.ndarray:
        """(level+1, N) NTT-domain plaintext -> complex slots (host)."""
        coeff = self.ctx.intt(pt_ntt, self.ctx.q_idx(level))
        return self.decode_coeffs(coeff, scale, level)

    def decode_coeffs(self, coeff: jnp.ndarray, scale: float,
                      level: int) -> np.ndarray:
        """(..., level+1, N) coefficient-domain residues (on the device)
        -> (..., slots) complex slots (host)."""
        with layer("lift", limbs=level + 1) as span:
            c, wide = self.lift(coeff, level)
            span.annotate(wide=wide)
        with layer("embed"):
            return self.embed_forward(c / scale)

    def lift(self, coeff: jnp.ndarray, level: int) -> Tuple[np.ndarray, int]:
        """Centred CRT lift of (..., level+1, N) residues to float64
        (..., N) on the host, bit-identical to ``float`` of
        `rns.crt_lift_centered` below 2^53; also how many values reach
        2^53 (where the float64 Horner may round otherwise)."""
        primes = self.ctx.q_primes[: level + 1]
        if len(primes) == 1:
            q = primes[0]
            c = np.asarray(coeff)[..., 0, :].astype(np.int64)
            return np.where(c > q // 2, c - q, c).astype(np.float64), 0
        digits, neg = jax.device_get(rns.mixed_radix_centred(
            coeff, self.ctx.lift_tables(level)))
        return rns.horner(digits, neg, primes)
