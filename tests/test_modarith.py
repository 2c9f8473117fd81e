"""Unit + property tests for modular arithmetic (all four reduction paths)."""
import numpy as np
import pytest
import jax.numpy as jnp

from _hyp import given, settings, st  # noqa: E402  (skips per-test)

from repro.core import modarith as ma
from repro.core.params import (find_ntt_primes, generic_ntt_primes, is_prime,
                               paper_params_bootstrap, paper_params_lola,
                               solinas_candidates)
from repro.core.params import test_params as make_test_params

# word32 moduli straight from the repo's own NTT-prime search — prime by
# construction (the search Miller-Rabin-filters every candidate), so the
# old hand-picked list and its "non-prime test modulus" runtime skip are
# gone for good.
_SOLINAS_MOD = next(m for m in find_ntt_primes(30, 17, 4) if m.is_solinas)
Q_SOLINAS = _SOLINAS_MOD.value               # 2^b - 2^s + 1 form
_SOL_B, _SOL_S = _SOLINAS_MOD.solinas
Q_GENERIC = generic_ntt_primes(30, 1 << 24, 1)[0]
Q_WIDE = find_ntt_primes(31, 12, 1)[0].value   # widest word32 prime


def _rand(rng, q, n=4096):
    return rng.integers(0, q, size=n, dtype=np.uint64)


@pytest.mark.parametrize("q", [Q_SOLINAS, Q_GENERIC, Q_WIDE])
def test_mulmod_paths_agree(rng, q):
    assert is_prime(q)          # by construction; never a skip
    a, b = _rand(rng, q), _rand(rng, q)
    ref = (a.astype(object) * b.astype(object)) % q
    aj, bj, qj = jnp.asarray(a), jnp.asarray(b), jnp.uint64(q)
    assert (np.asarray(ma.mulmod(aj, bj, qj)).astype(object) == ref).all()
    mu = jnp.uint64(ma.barrett_mu(q))
    assert (np.asarray(ma.mulmod_barrett(aj, bj, qj, mu)).astype(object) == ref).all()
    qi = jnp.uint64(ma.mont_qinv_neg(q))
    r2 = jnp.uint64(ma.mont_r2(q))
    am = ma.to_mont(aj, qj, qi, r2)
    assert (np.asarray(ma.mont_mul(am, bj, qj, qi)).astype(object) == ref).all()


def test_solinas_reduction(rng):
    q = Q_SOLINAS
    a, b = _rand(rng, q), _rand(rng, q)
    ref = (a.astype(object) * b.astype(object)) % q
    got = ma.mulmod_solinas(jnp.asarray(a), jnp.asarray(b), jnp.uint64(q),
                            _SOL_B, _SOL_S)
    assert (np.asarray(got).astype(object) == ref).all()


def test_addsub_neg(rng):
    q = Q_GENERIC
    a, b = _rand(rng, q), _rand(rng, q)
    qj = jnp.uint64(q)
    assert (np.asarray(ma.addmod(jnp.asarray(a), jnp.asarray(b), qj))
            == (a.astype(object) + b.astype(object)) % q).all()
    assert (np.asarray(ma.submod(jnp.asarray(a), jnp.asarray(b), qj))
            == (a.astype(object) - b.astype(object)) % q).all()
    assert (np.asarray(ma.negmod(jnp.asarray(a), qj))
            == (-a.astype(object)) % q).all()


@settings(max_examples=200, deadline=None)
@given(a=st.integers(0, 2**63 - 1), b=st.integers(0, 2**63 - 1))
def test_mulhi64_property(a, b):
    got = int(np.asarray(ma.mulhi64(jnp.uint64(a), jnp.uint64(b))))
    assert got == (a * b) >> 64


@settings(max_examples=100, deadline=None)
@given(a=st.integers(0, Q_SOLINAS - 1), b=st.integers(0, Q_SOLINAS - 1),
       c=st.integers(0, Q_SOLINAS - 1))
def test_ring_axioms_property(a, b, c):
    """Field axioms mod q via the vectorized ops (distributivity etc.)."""
    q = jnp.uint64(Q_SOLINAS)
    aj, bj, cj = jnp.uint64(a), jnp.uint64(b), jnp.uint64(c)
    left = ma.mulmod(aj, ma.addmod(bj, cj, q), q)
    right = ma.addmod(ma.mulmod(aj, bj, q), ma.mulmod(aj, cj, q), q)
    assert int(left) == int(right)
    assert int(ma.mulmod(aj, bj, q)) == int(ma.mulmod(bj, aj, q))


def test_prime_search_properties():
    for log_n in (8, 10, 12):
        mods = find_ntt_primes(30, log_n, 4)
        assert len(set(m.value for m in mods)) == 4
        for m in mods:
            assert is_prime(m.value)
            assert (m.value - 1) % (1 << (log_n + 1)) == 0
            if m.solinas:
                b, s = m.solinas
                assert m.value == (1 << b) - (1 << s) + 1


def test_solinas_candidates_ntt_friendly():
    for p, b, s in solinas_candidates(31, 13):
        assert is_prime(p) and (p - 1) % (1 << 13) == 0


@pytest.mark.parametrize("make", [paper_params_bootstrap, paper_params_lola,
                                  make_test_params],
                         ids=["bootstrap", "lola", "test"])
def test_named_params_fit_word32(make):
    """The u32 Pallas kernels need every modulus < 2^31 (word32 mode)."""
    assert max(m.value for m in make().moduli) < 2 ** 31
