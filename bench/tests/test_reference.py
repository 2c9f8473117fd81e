"""The plain reference agrees with the program's own plaintext oracle
(``reference_eval``) on the uncompiled traces, and derives the server's
constants by the rule the serving backend states."""
import os

import numpy as np
import pytest

from bench import harness, reference

SLOTS = 256
CONFIGS = ["bench/configs/ckks_boot_n16.json",
           "bench/configs/ckks_lola_n14.json"]


def _specs():
    out = []
    for path in CONFIGS:
        cfg = harness.load_json(os.path.join(harness.ROOT, path))
        out += list(cfg["programs"].items())
    return out


def _trace(spec):
    from repro.core.trace import trace_program
    from repro.runtime import workloads
    fn = getattr(workloads, spec["factory"])
    if "args" in spec:
        fn = fn(*spec["args"])
    consts = spec["consts"]
    if isinstance(consts, str):
        consts = getattr(workloads, consts)(*spec.get("consts_args", []))
    return trace_program(fn, int(spec["inputs"]), tuple(consts))


@pytest.mark.parametrize("program,spec", _specs(), ids=lambda v: str(v)[:8])
def test_reference_matches_oracle_on_uncompiled_trace(program, spec):
    from repro.compiler.interp import reference_eval
    ref = reference.Reference(program, spec, SLOTS)
    rows = np.random.default_rng(3).uniform(-0.8, 0.8, size=(3, SLOTS))
    inputs = [rows] + [np.broadcast_to(a, rows.shape) for a in ref.aux]
    want = reference_eval(_trace(spec), inputs, ref.consts)[0]
    got = ref.evaluate(rows)
    np.testing.assert_allclose(got, np.real(want), rtol=1e-12, atol=1e-12)


def test_server_constants_follow_the_backend_rule():
    from repro.core.params import test_params
    from repro.runtime.ciphertext_backend import CiphertextBackend
    cell = harness.load_cell("ckks_boot_n16.helr_closed")
    params = test_params(log_n=8, n_levels=4, dnum=2)
    be = CiphertextBackend(params, use_kernels=False)
    trace = _trace(cell.program_spec)
    ref = reference.Reference(cell.program, cell.program_spec, params.slots)
    got = be.workload_consts(cell.program, trace)
    for name, v in ref.consts.items():
        np.testing.assert_array_equal(got[name], v)
    np.testing.assert_array_equal(be._aux_input(cell.program, 1, 1)[0],
                                  ref.aux[0])


def test_lower_precision_moves_the_reference():
    cell = harness.load_cell("ckks_boot_n16.matvec_steady")
    ref = reference.Reference(cell.program, cell.program_spec, SLOTS)
    rows = np.random.default_rng(4).uniform(-1, 1, size=(2, SLOTS))
    exact = ref.evaluate(rows)
    gaps = [np.abs(ref.evaluate(rows, reference.precision(r)) - exact).max()
            for r in ("float32", "bfloat16", "float8_e4m3fn")]
    assert 0 < gaps[0] < gaps[1] < gaps[2]
