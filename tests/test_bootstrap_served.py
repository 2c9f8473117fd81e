"""Real CKKS bootstrapping on the served path: the factored DFT against
the dense matrices, EvalMod's polynomial against the scaled sine, the
engine's batched bootstrap, HELR training served through
`CiphertextBackend` against the benchmark's plain reference, key
accounting, and the structured error for a refresh the settings cannot
reach. Small ring (logN=6) on the CPU, seeded keys and data."""
import types

import numpy as np
import pytest

from bench.reference import Reference
from repro.compiler import PassConfig, optimize_trace
from repro.compiler.engine import CkksEngine
from repro.core.bootstrap import _dft_levels, bootstrap_plan, dense_dft
from repro.core.encoder import CkksEncoder
from repro.core.params import (BootstrapLevelError, CkksParams,
                               paper_params_bootstrap)
from repro.core.params import test_params as toy_params
from repro.core.pipeline import MemoryModel
from repro.core.trace import evk_bytes, trace_program
from repro.obs.hook import RING
from repro.runtime import Batch, CiphertextBackend, MetricsRegistry, Request
from repro.runtime.compile_cache import CompileCache
from repro.runtime.workloads import (HELR_CONSTS, make_helr_iter,
                                     make_helr_train, make_poly_eval,
                                     poly_consts)

# 32 slots; CoeffToSlot/SlotToCoeff in 2 levels each, EvalMod a degree-15
# cosine and 3 double angles (8 levels): 12 levels, a refresh lands at 4.
# h=8 gives |I| a standard deviation of 0.87, which K=6 bounds. At 32
# slots coefficients are only about 6x smaller than slot values, so the
# message ratio is 4, where logN=16 takes 1/16.
BTS = CkksParams(log_n=6, log_scale=26, n_levels=16, dnum=2,
                 first_mod_bits=30, scale_mod_bits=26, special_mod_bits=30,
                 hamming_weight_sk=8, bts_dft_levels=2,
                 bts_cheb_degree=15, bts_double_angle=3, bts_k_range=6,
                 bts_msg_ratio=4.0)
MEM = MemoryModel(n_partitions=4, partition_bytes=256 * 2 ** 10)
HELR_TRAIN = {"factory": "make_helr_train", "inputs": 2,
              "reference": "helr_train",
              "reference_args": {"iters": 2, "rot_steps": [1, 2, 4, 8]}}


@pytest.fixture(scope="module")
def backend():
    return CiphertextBackend(BTS, seed=5, use_kernels=False)


def _bit_reversal(log_s: int) -> np.ndarray:
    s = 1 << log_s
    br = [int(format(i, f"0{log_s}b")[::-1], 2) for i in range(s)]
    return np.eye(s)[br]


def _apply(levels, x: np.ndarray) -> np.ndarray:
    """The factored levels' baby-step giant-step evaluation, in numpy,
    with rot(v, k)[p] = v[p + k]."""
    for lv in levels:
        out = 0
        for i, terms in lv.terms.items():
            inner = sum(pt * np.roll(x, -lv.gap * j) for j, pt in terms)
            out = out + np.roll(inner, -lv.gap * lv.baby * i)
        x = out
    return x


@pytest.mark.parametrize("log_n,n_levels", [(7, 1), (7, 2), (7, 3), (6, 2),
                                            (5, 4)])
def test_factored_dft_matches_dense(log_n, n_levels):
    """CoeffToSlot's levels multiply to the dense CoeffToSlot matrix with
    its output bit-reversed, SlotToCoeff's to the dense SlotToCoeff
    matrix reading bit-reversed input."""
    n = 1 << log_n
    s = n // 2
    a_cts, a_stc = dense_dft(CkksEncoder(types.SimpleNamespace(n=n)))
    perm = _bit_reversal(log_n - 1)
    eye = np.eye(s, dtype=np.complex128)
    cts = np.stack([_apply(_dft_levels(log_n - 1, n_levels, True), eye[:, k])
                    for k in range(s)], axis=1)
    stc = np.stack([_apply(_dft_levels(log_n - 1, n_levels, False),
                           eye[:, k]) for k in range(s)], axis=1)
    np.testing.assert_allclose(cts, perm @ a_cts, atol=1e-12)
    np.testing.assert_allclose(stc, a_stc @ perm, atol=1e-12)


def test_paper_plan_shape():
    """At logN=16 each transform is 4 levels of at most 31 diagonals,
    sharing 32 rotations; the keys and plaintexts are what the pinned
    accounting counts."""
    plan = bootstrap_plan(paper_params_bootstrap())
    assert [len(lv.diags) for lv in plan.cts] == [16, 31, 31, 15]
    assert [len(lv.diags) for lv in plan.stc] == [15, 31, 31, 16]
    assert len(plan.rotation_steps) == 32
    assert len(plan.galois_elements()) == 33          # and conjugation
    assert paper_params_bootstrap().bts_output_level == 6


@pytest.mark.parametrize("params,most", [(paper_params_bootstrap(), 1e-7),
                                         (BTS, 1e-4)], ids=["paper", "small"])
def test_evalmod_polynomial_against_scaled_sine(params, most):
    """EvalMod's Chebyshev cosine and double angles against
    (q0/2pi) sin(2pi t/q0) over |t| <= (K+1) q0, within the bound the
    plan states (its series' tail grown by the double angles)."""
    plan = bootstrap_plan(params)
    q0 = params.moduli[0].value
    t = np.linspace(-(params.bts_k_range + 1), params.bts_k_range + 1,
                    200001) * q0
    got = q0 / (2 * np.pi) * plan.eval_mod_plain(t / q0)
    want = q0 / (2 * np.pi) * np.sin(2 * np.pi * t / q0)
    bound = q0 / (2 * np.pi) * plan.eval_mod_bound
    assert np.abs(got - want).max() <= bound
    assert plan.eval_mod_bound < most


def test_engine_bootstrap_batch(backend):
    """A CtBatch of 2 at level 0 refreshed to the highest level the
    settings reach, within the derived tolerance of its input, at the
    scale of a fresh encryption, with the layer records of its phases."""
    eng = backend.engine
    rng = np.random.default_rng(11)
    v = rng.uniform(-0.35, 0.35, (2, BTS.slots)) \
        + 1j * rng.uniform(-0.35, 0.35, (2, BTS.slots))
    cb = eng.encrypt_batch(v, 0)
    target = BTS.bts_output_level
    seen = len(RING.records())
    out = eng.bootstrap(cb, target)
    assert (out.level, out.scale, out.batch) == (target,
                                                 2.0 ** BTS.log_scale, 2)
    err = np.abs(eng.decode_batch(out) - v).max()
    assert err <= eng.bootstrap_tolerance, err
    recs = RING.records()[seen:]
    names = [r.name for r in recs]
    for name in ("bootstrap", "mod_raise", "cts", "evalmod", "stc"):
        assert names.count(name) == 1, names
    boot = recs[names.index("bootstrap")].attrs
    plan = bootstrap_plan(BTS)
    n_rot = sum(len({j for _, ts in lv.terms.items() for j, _ in ts} - {0})
                + len([i for i in lv.terms if i]) for lv in plan.cts
                + plan.stc)
    assert boot["keyswitches"] == n_rot + 1 + 14 + 3  # ladder T_2..T_15
    assert (boot["level_in"], boot["level_out"]) == (0, target)
    # a refresh cannot land above what the settings reach
    with pytest.raises(BootstrapLevelError):
        eng.bootstrap(cb, target + 1)


def test_helr_train_served_matches_reference(backend):
    """Two HELR steps served through the backend at the settings' output
    level, with the compiler's bootstrap insertion, against the
    benchmark's plain reference of the same program."""
    start = BTS.bts_output_level
    trace = trace_program(make_helr_train(2, (1, 2, 4, 8)), 2,
                          const_names=HELR_CONSTS)
    sched = CompileCache().get_schedule(
        trace, BTS, MEM, pass_config=PassConfig(start_level=start))
    n_boot = sum(op.kind == "bootstrap" for op in sched.trace.ops)
    assert n_boot >= 1
    rng = np.random.default_rng(3)
    reqs = [Request(i, f"t{i % 2}", "helr_train", arrival_s=0.0,
                    slots_needed=16, payload=rng.uniform(-0.5, 0.5, 16))
            for i in range(4)]
    batch = Batch("helr_train", reqs, [reqs[:2], reqs[2:]], formed_s=0.0)
    metrics = MetricsRegistry(MEM.n_partitions)
    backend.execute(sched, batch, key_cache=None, metrics=metrics,
                    workload="helr_train")
    want = Reference("helr_train", HELR_TRAIN, BTS.slots).evaluate(
        backend._pack(batch, 2).real)
    err = np.abs(batch.outputs[0] - want).max()
    assert err <= backend.engine.bootstrap_tolerance, err
    assert np.abs(want).max() > 0.1                    # non-degenerate


def test_pinned_key_bytes_count_bootstrap_keys():
    """The bootstrap's keys and plaintexts are pinned where a trace
    bootstraps on a set with bootstrap settings; a set without them
    counts none."""
    paper = paper_params_bootstrap()
    nb = evk_bytes(paper)
    trace = trace_program(make_helr_train(2, (1, 2, 4, 8)), 2,
                          const_names=HELR_CONSTS)
    plan = bootstrap_plan(paper)
    n_keys, total = CiphertextBackend.pinned_key_bytes(
        [trace], paper, PassConfig(start_level=paper.bts_output_level))
    assert n_keys >= 1 + len(plan.galois_elements())
    assert total == n_keys * nb + plan.plaintext_limbs(23) * paper.n * 8
    assert 5e9 < total < 8e9
    no_bts = CkksParams(log_n=16, log_scale=28, n_levels=23, dnum=4,
                        first_mod_bits=31, scale_mod_bits=28,
                        special_mod_bits=31)
    helr = trace_program(make_helr_iter(), 2, const_names=HELR_CONSTS)
    assert CiphertextBackend.pinned_key_bytes(
        [helr], no_bts, PassConfig(start_level=20)) == (5, 5 * nb)


def test_no_bootstrap_settings_builds_nothing():
    """A set without bootstrap settings takes the test-scale refresh,
    exact and with no bootstrap key or plaintext; above logN=12 (every
    benchmark configuration) it refuses."""
    eng = CkksEngine(toy_params(log_n=6, n_levels=3, dnum=1), seed=2)
    v = np.random.default_rng(0).uniform(-1, 1, (2, 32))
    out = eng.bootstrap(eng.encrypt_batch(v, 0), 3)
    assert out.level == 3 and not eng._gks and not eng._bts_pts
    np.testing.assert_allclose(eng.decode_batch(out), v, atol=eng.tolerance)
    big = CkksEngine(toy_params(log_n=13, n_levels=1, dnum=1), seed=2)
    with pytest.raises(BootstrapLevelError):
        big.bootstrap(big.encrypt_batch(np.zeros((1, 4096)), 0), 1)


def test_unreachable_refresh_is_a_compile_error():
    """A shallow set asked for a real refresh, or a deep one asked to
    refresh above its reach, fails when the schedule is compiled."""
    shallow = CkksParams(log_n=6, log_scale=26, n_levels=6, dnum=2,
                         first_mod_bits=30, scale_mod_bits=26,
                         special_mod_bits=30, bts_dft_levels=2,
                         bts_cheb_degree=15)
    poly = trace_program(make_poly_eval(8), 1, const_names=poly_consts(8))
    with pytest.raises(BootstrapLevelError) as e:
        optimize_trace(poly, shallow, PassConfig(start_level=4))
    assert e.value.reachable == 6 - shallow.bts_levels < 0
    with pytest.raises(BootstrapLevelError) as e:
        optimize_trace(poly, BTS, PassConfig(start_level=6))
    assert (e.value.target, e.value.reachable) == (6, 4)
    out, _ = optimize_trace(poly, BTS, PassConfig(start_level=4))
    assert any(op.kind == "bootstrap" for op in out.ops)
