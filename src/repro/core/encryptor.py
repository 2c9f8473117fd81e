"""Key generation, encryption, decryption for full-RNS CKKS.

Sampling conventions (standard RNS practice):
* uniform ring elements are sampled directly in the NTT domain, limb-wise
  independent (valid by the CRT isomorphism R_Q ≅ ∏_i Z_{q_i}^N);
* small elements (secret, errors) are sampled as integer coefficient
  vectors, reduced per limb, then NTT'd — the *same* small polynomial in
  every limb.

Key-switching keys implement the generalized (Han–Ki) gadget: for digit d
with modulus group Q_d,   g_d = P * Qhat_d * [Qhat_d^{-1}]_{Q_d}  (mod each
prime of Q∪P), and  ksk_d = (-a_d s + e_d + g_d * s_src , a_d).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import modarith as ma
from repro.core.ciphertext import (Ciphertext, KeySwitchKey, Plaintext,
                                   PublicKey, SecretKey)
from repro.core.context import CkksContext


class CkksEncryptor:

    def __init__(self, ctx: CkksContext, seed: int = 2024):
        self.ctx = ctx
        self.rng = np.random.default_rng(seed)

    # -- sampling -----------------------------------------------------------

    def _sample_uniform_ntt(self, idx: Sequence[int],
                            shape_prefix=()) -> jnp.ndarray:
        primes = np.array([self.ctx.primes[i] for i in idx], dtype=np.uint64)
        out = np.empty(shape_prefix + (len(idx), self.ctx.n), dtype=np.uint64)
        for k, p in enumerate(primes):
            out[..., k, :] = self.rng.integers(0, p, size=shape_prefix + (self.ctx.n,),
                                               dtype=np.uint64)
        return jnp.asarray(out)

    def _sample_error_coeff(self) -> np.ndarray:
        e = np.round(self.rng.normal(0.0, self.ctx.params.error_std,
                                     size=self.ctx.n)).astype(np.int64)
        return e

    def _sample_ternary_coeff(self, hamming: Optional[int] = None) -> np.ndarray:
        n = self.ctx.n
        h = hamming or self.ctx.params.hamming_weight_sk
        s = np.zeros(n, dtype=np.int64)
        pos = self.rng.choice(n, size=h, replace=False)
        s[pos] = self.rng.choice(np.array([-1, 1]), size=h)
        return s

    def _small_to_ntt(self, coeffs: np.ndarray, idx: Sequence[int]) -> jnp.ndarray:
        primes = np.array([self.ctx.primes[i] for i in idx], dtype=np.int64)
        limbs = (coeffs[None, :] % primes[:, None]).astype(np.uint64)
        return self.ctx.ntt(jnp.asarray(limbs), idx)

    # -- keygen -------------------------------------------------------------

    def keygen(self) -> SecretKey:
        s = self._sample_ternary_coeff()
        all_idx = list(range(self.ctx.n_q + self.ctx.n_p))
        return SecretKey(s_ntt=self._small_to_ntt(s, all_idx),
                         s_coeff_ternary=jnp.asarray(s.astype(np.int8)))

    def public_keygen(self, sk: SecretKey) -> PublicKey:
        idx = self.ctx.q_idx(self.ctx.params.n_levels)
        q = self.ctx.q_all[np.array(idx)]
        a = self._sample_uniform_ntt(idx)
        e = self._small_to_ntt(self._sample_error_coeff(), idx)
        s = sk.s_ntt[np.array(idx)]
        b = ma.submod(e, ma.mulmod(a, s, q[:, None]), q[:, None])
        return PublicKey(data=jnp.stack([b, a]))

    def _ksk_gen(self, sk: SecretKey, target_ntt: jnp.ndarray) -> KeySwitchKey:
        """KSK switching FROM the key whose full-basis NTT rep is target_ntt
        TO sk. target_ntt: (n_q+n_p, N)."""
        ctx = self.ctx
        all_idx = list(range(ctx.n_q + ctx.n_p))
        q = ctx.q_all
        s = sk.s_ntt
        big_p = ctx.big_p
        big_q_full = 1
        for p in ctx.q_primes:
            big_q_full *= p
        digits = ctx.params.digit_indices(ctx.params.n_levels)
        ksk = []
        for d, J in enumerate(digits):
            q_d = 1
            for j in J:
                q_d *= ctx.q_primes[j]
            qhat_d = big_q_full // q_d
            g_d = big_p * qhat_d * pow(qhat_d % q_d, -1, q_d)
            g_limbs = jnp.asarray(np.array(
                [g_d % ctx.primes[i] for i in all_idx], dtype=np.uint64))
            a = self._sample_uniform_ntt(all_idx)
            e = self._small_to_ntt(self._sample_error_coeff(), all_idx)
            body = ma.mulmod(target_ntt, g_limbs[:, None], q[:, None])
            b = ma.addmod(
                ma.submod(e, ma.mulmod(a, s, q[:, None]), q[:, None]),
                body, q[:, None])
            ksk.append(jnp.stack([b, a]))
        return KeySwitchKey(data=jnp.stack(ksk))

    def relin_keygen(self, sk: SecretKey) -> KeySwitchKey:
        q = self.ctx.q_all
        s2 = ma.mulmod(sk.s_ntt, sk.s_ntt, q[:, None])
        return self._ksk_gen(sk, s2)

    def galois_keygen(self, sk: SecretKey,
                      elements: Sequence[int]) -> Dict[int, KeySwitchKey]:
        """Keys for sigma_k(s) -> s, per Galois element k."""
        out = {}
        for k in elements:
            perm = self.ctx.eval_perm(k)
            s_rot = sk.s_ntt[:, perm]
            out[k] = self._ksk_gen(sk, s_rot)
        return out

    def rotation_keygen(self, sk: SecretKey,
                        steps: Sequence[int]) -> Dict[int, KeySwitchKey]:
        elts = sorted({self.ctx.rotation_element(st) for st in steps})
        return self.galois_keygen(sk, elts)

    # -- encrypt / decrypt ---------------------------------------------------

    def encrypt_sk(self, pt: Plaintext, sk: SecretKey) -> Ciphertext:
        idx = self.ctx.q_idx(pt.level)
        a = self._sample_uniform_ntt(idx)
        e = self._small_to_ntt(self._sample_error_coeff(), idx)
        return Ciphertext(_sk_encrypt(a, e, sk.s_ntt, pt.data,
                                      self.ctx.q_all), pt.level, pt.scale)

    def encrypt_pk(self, pt: Plaintext, pk: PublicKey) -> Ciphertext:
        ctx = self.ctx
        idx = ctx.q_idx(pt.level)
        q = ctx.q_all[np.array(idx)]
        n_limbs = len(idx)
        u = self._small_to_ntt(self._sample_ternary_coeff(), idx)
        e0 = self._small_to_ntt(self._sample_error_coeff(), idx)
        e1 = self._small_to_ntt(self._sample_error_coeff(), idx)
        pk0 = pk.data[0, :n_limbs]
        pk1 = pk.data[1, :n_limbs]
        b = ma.addmod(ma.addmod(ma.mulmod(pk0, u, q[:, None]), e0, q[:, None]),
                      pt.data, q[:, None])
        a = ma.addmod(ma.mulmod(pk1, u, q[:, None]), e1, q[:, None])
        return Ciphertext(jnp.stack([b, a]), pt.level, pt.scale)

    def decrypt(self, ct: Ciphertext, sk: SecretKey) -> Plaintext:
        m = decrypt_data(ct.data, sk.s_ntt, self.ctx.q_all)
        return Plaintext(m, ct.level, ct.scale)


# one compiled program per shape: run op by op, every u64 primitive would
# be compiled on its own (seconds each on an accelerator)

@jax.jit
def _sk_encrypt(a, e, s, m, q):
    """(e - a·s + m, a) over the first L limbs of s and q, L those of a
    (all NTT domain)."""
    n_limbs = a.shape[-2]
    s, q = s[:n_limbs], q[:n_limbs, None]
    b = ma.addmod(ma.submod(e, ma.mulmod(a, s, q), q), m, q)
    return jnp.stack([b, a])


@jax.jit
def decrypt_data(data, s, q):
    """c0 + c1·s over the first L limbs of s and q; data (..., 2, L, N)."""
    n_limbs = data.shape[-2]
    s, q = s[:n_limbs], q[:n_limbs, None]
    return ma.addmod(data[..., 0, :, :],
                     ma.mulmod(data[..., 1, :, :], s, q), q)
