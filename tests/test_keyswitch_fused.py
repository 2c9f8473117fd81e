"""Differential tests for the fused Pallas keyswitch pipeline.

The fused route (repro/kernels/keyswitch.py) claims BIT-exactness, not
closeness: u32 Montgomery arithmetic computes the same canonical
residues as the u64 library path, so every assertion here is
``assert_array_equal``, never allclose. Covered:

* kernel-level equality vs core/ops.key_switch across levels, digit
  counts (dnum 1/2/3, including ragged tail digits), and batch sizes;
* the dispatch-per-stage staged baseline (fig14's comparison anchor)
  is ALSO bit-equal, and the fused/staged dispatch counts match a
  golden snapshot (tests/golden/dispatch_counts.json, REGEN_GOLDENS=1);
* engine-level decrypt equality fused-vs-library on real workload
  traces, rotation steps, conjugation, and hypothesis-random traces.
"""
import json
import os

import numpy as np
import pytest
import jax.numpy as jnp

from _hyp import given, settings, st  # noqa: E402  (skips per-test)

from repro.compiler.engine import CkksEngine
from repro.core import ops as hops
from repro.core.context import CkksContext
from repro.core.encryptor import CkksEncryptor
from repro.core.params import test_params as make_test_params
from repro.core.trace import trace_program
from repro.kernels import common as kcom
from repro.kernels.keyswitch import FusedKeySwitch, keyswitch_staged

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "golden",
                           "dispatch_counts.json")

LOG_N = 7
N_LEVELS = 4


def _setup(dnum):
    params = make_test_params(log_n=LOG_N, n_levels=N_LEVELS, dnum=dnum,
                              log_scale=26)
    ctx = CkksContext(params)
    enc = CkksEncryptor(ctx, seed=11)
    sk = enc.keygen()
    rk = enc.relin_keygen(sk)
    return ctx, enc, sk, rk


def _rand_d2(ctx, batch, level, seed=0):
    rng = np.random.default_rng(seed)
    l = level + 1
    d2 = np.empty((batch, l, ctx.n), dtype=np.uint64)
    for j in range(l):
        d2[:, j] = rng.integers(0, ctx.primes[j], size=(batch, ctx.n),
                                dtype=np.uint64)
    return jnp.asarray(d2)


# ---------------------------------------------------------------------------
# kernel level: fused == reference == staged, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dnum", [1, 2, 3])
def test_fused_bit_equal_reference_across_levels(dnum):
    """Every level exercises a different digit decomposition (including
    ragged tail digits when alpha doesn't divide level+1)."""
    ctx, _, _, rk = _setup(dnum)
    fks = FusedKeySwitch(ctx)
    for level in range(1, N_LEVELS + 1):
        d2 = _rand_d2(ctx, 2, level, seed=level)
        km = fks.ksk_mont("relin", rk.data)
        e0, e1 = fks.apply(d2, level, km, interpret=True)
        for i in range(d2.shape[0]):
            r0, r1 = hops.key_switch(ctx, d2[i], level, rk)
            np.testing.assert_array_equal(np.asarray(e0[i]), np.asarray(r0))
            np.testing.assert_array_equal(np.asarray(e1[i]), np.asarray(r1))


@pytest.mark.parametrize("dnum", [1, 2])
def test_staged_bit_equal_reference(dnum):
    ctx, _, _, rk = _setup(dnum)
    level = N_LEVELS
    d2 = _rand_d2(ctx, 1, level, seed=3)
    s0, s1 = keyswitch_staged(ctx, d2[0], level, rk, interpret=True)
    r0, r1 = hops.key_switch(ctx, d2[0], level, rk)
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(r0))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(r1))


def test_fused_galois_key_bit_equal():
    """The same fused pipeline serves Galois keys (rotation keyswitch)."""
    ctx, enc, sk, _ = _setup(2)
    elt = ctx.rotation_element(3)
    gk = enc.galois_keygen(sk, [elt])[elt]
    fks = FusedKeySwitch(ctx)
    level = N_LEVELS - 1
    d2 = _rand_d2(ctx, 2, level, seed=5)
    km = fks.ksk_mont(("gk", elt), gk.data)
    e0, e1 = fks.apply(d2, level, km, interpret=True)
    for i in range(d2.shape[0]):
        r0, r1 = hops.key_switch(ctx, d2[i], level, gk)
        np.testing.assert_array_equal(np.asarray(e0[i]), np.asarray(r0))
        np.testing.assert_array_equal(np.asarray(e1[i]), np.asarray(r1))


# ---------------------------------------------------------------------------
# dispatch accounting: fused is a >=4x reduction, snapshot-pinned
# ---------------------------------------------------------------------------

def _measure_dispatches(dnum, level):
    ctx, _, _, rk = _setup(dnum)
    fks = FusedKeySwitch(ctx)
    d2 = _rand_d2(ctx, 1, level, seed=7)
    km = fks.ksk_mont("relin", rk.data)
    kcom.reset_dispatch_count()
    fks.apply(d2, level, km, interpret=True)
    fused = kcom.dispatch_count()
    kcom.reset_dispatch_count()
    keyswitch_staged(ctx, d2[0], level, rk, interpret=True)
    staged = kcom.dispatch_count()
    return {"fused": fused, "staged": staged,
            "digits": len(ctx.params.digit_indices(level))}


def test_dispatch_counts_golden():
    """Fused launch count is flat (4) while staged grows 7*digits + 10;
    the golden pins both so a regression that quietly re-splits the
    pipeline (or miscounts the baseline) fails here, not in fig14."""
    measured = {}
    for dnum in (1, 2, 3):
        for level in (1, N_LEVELS):
            m = _measure_dispatches(dnum, level)
            measured[f"dnum{dnum}_level{level}"] = m
            assert m["fused"] == FusedKeySwitch.DISPATCHES_PER_APPLY
            assert m["staged"] == 7 * m["digits"] + 10
            assert m["staged"] >= 4 * m["fused"], m
    if os.environ.get("REGEN_GOLDENS"):
        with open(GOLDEN_PATH, "w") as f:
            json.dump(measured, f, indent=2, sort_keys=True)
        pytest.skip("regenerated dispatch_counts.json")
    with open(GOLDEN_PATH) as f:
        golden = json.load(f)
    assert measured == golden


def test_dispatch_count_independent_of_batch():
    ctx, _, _, rk = _setup(2)
    fks = FusedKeySwitch(ctx)
    km = fks.ksk_mont("relin", rk.data)
    for batch in (1, 4):
        d2 = _rand_d2(ctx, batch, N_LEVELS, seed=batch)
        kcom.reset_dispatch_count()
        fks.apply(d2, N_LEVELS, km, interpret=True)
        assert kcom.dispatch_count() == FusedKeySwitch.DISPATCHES_PER_APPLY


# ---------------------------------------------------------------------------
# engine level: use_kernels route decrypt-equal on real traces
# ---------------------------------------------------------------------------

ENGINE_PARAMS = make_test_params(log_n=7, n_levels=5, dnum=2, log_scale=26)


@pytest.fixture(scope="module")
def engines():
    return (CkksEngine(ENGINE_PARAMS, seed=7),
            CkksEngine(ENGINE_PARAMS, seed=7, use_kernels=True))


def _run_both(engines, fn, n_in, const_names, seed=0, start_level=4):
    lib, fus = engines
    rng = np.random.default_rng(seed)
    tr = trace_program(fn, n_in, const_names=const_names or ())
    consts = {c: rng.uniform(-0.25, 0.25, size=ENGINE_PARAMS.slots)
              for c in (const_names or ())}
    ins = [rng.uniform(-0.5, 0.5, size=(2, ENGINE_PARAMS.slots))
           for _ in range(n_in)]
    a = lib.run_batch(tr, ins, consts, start_level=start_level)
    b = fus.run_batch(tr, ins, consts, start_level=start_level)
    for va, vb in zip(a, b):
        np.testing.assert_array_equal(va, vb)


def test_engine_hmul_chain_decrypt_equal(engines):
    def fn(x, y):
        z = x * y
        return z * z
    _run_both(engines, fn, 2, None, seed=1)


@pytest.mark.parametrize("step", [1, -3, 7])
def test_engine_rotation_decrypt_equal(engines, step):
    def fn(x, consts=None):
        return (x * consts["w"]).rotate(step) + x
    _run_both(engines, fn, 1, ["w"], seed=20 + step)


def test_engine_conjugate_decrypt_equal(engines):
    def fn(x):
        return x.conjugate() + x
    _run_both(engines, fn, 1, None, seed=9)


def test_engine_lazy_hmul_decrypt_equal(engines):
    """Lazy (unrescaled) hmul exercises the fused route's rescale-
    deferral split."""
    lib, fus = engines

    def fn(x, y):
        return (x * y).rescale()
    tr = trace_program(fn, 2)
    for op in tr.ops:
        if op.kind == "hmul":
            op.meta["lazy"] = True
    rng = np.random.default_rng(4)
    ins = [rng.uniform(-0.5, 0.5, size=(2, ENGINE_PARAMS.slots))
           for _ in range(2)]
    a = lib.run_batch(tr, ins, {}, start_level=4)
    b = fus.run_batch(tr, ins, {}, start_level=4)
    for va, vb in zip(a, b):
        np.testing.assert_array_equal(va, vb)


# ---------------------------------------------------------------------------
# hypothesis: random well-formed traces, fused == library bitwise
# ---------------------------------------------------------------------------

from test_properties import build_trace, trace_specs  # noqa: E402


@pytest.fixture(scope="module")
def small_engines():
    params = make_test_params(log_n=7, n_levels=6, dnum=2, log_scale=26)
    return (params,
            CkksEngine(params, seed=7),
            CkksEngine(params, seed=7, use_kernels=True))


@settings(max_examples=5, deadline=None)
@given(spec=trace_specs(), seed=st.integers(0, 2 ** 31 - 1))
def test_engine_random_traces_decrypt_equal(spec, seed, small_engines):
    """For ANY well-formed random trace: the fused-kernel engine decodes
    bit-identically to the library engine (same keys, same seed)."""
    params, lib, fus = small_engines
    trace = build_trace(*spec)
    rng = np.random.default_rng(seed)
    ins = [0.3 * (rng.normal(size=(1, params.slots))
                  + 1j * rng.normal(size=(1, params.slots)))
           for _ in trace.inputs]
    cs = {f"c{i}": 0.25 * rng.normal(size=params.slots) for i in range(3)}
    a = lib.run_batch(trace, ins, cs, start_level=5)
    b = fus.run_batch(trace, ins, cs, start_level=5)
    for va, vb in zip(a, b):
        np.testing.assert_array_equal(va, vb)
