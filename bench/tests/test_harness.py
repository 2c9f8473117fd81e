"""CPU rehearsal of each cell's code path at small parameters, and the
faults the correctness check has to catch.

Not a measurement: the kernels run in interpret mode (so every result
reads ``correct: false`` on ``interpret_dispatches``), and the device is a
stand-in that skips the harness's look for a chip. Everything else is the
run as ``bench/run.py`` makes it: set-up, the window, the metric readers,
the comparison with the plain reference."""
import time
import types

import numpy as np
import pytest

from bench import harness
from bench.run import measure

SMALL = {"log_n": 10, "log_scale": 26, "n_levels": 8, "dnum": 2,
         "first_mod_bits": 30, "scale_mod_bits": 26, "special_mod_bits": 30}
# loads that fill every ciphertext of a batch at these small parameters
MIXES = {"ckks_boot_n16.matvec_steady": {"rate_rps": 40.0,
                                         "slots": [100, 200]},
         "ckks_lola_n14.lola_steady": {"rate_rps": 150.0, "slots": [49, 49]},
         "ckks_boot_n16.helr_closed": {"clients": 64, "tenants": 4,
                                       "slots": [64, 64]}}
STANDIN = types.SimpleNamespace(platform="cpu", device_kind="TPU v5 lite",
                                memory_stats=lambda: {})


def small_cell(name):
    cell = harness.load_cell(name)
    cell.config = dict(cell.config, params=SMALL, start_level=7)
    cell.mix = dict(cell.mix, **MIXES[name])
    return cell


@pytest.fixture(scope="module", params=sorted(MIXES))
def rig(request):
    cell = small_cell(request.param)
    counter = harness.CompileCounter()
    server = harness.Server(cell, layers=True)
    server.warmup()
    return cell, server, counter


def run(rig, trace=False, seed=2**31 + 7):
    cell, server, counter = rig
    return measure(cell, seed=seed, seconds=2.0, trace=trace,
                   devices=[STANDIN], t_start=time.perf_counter(),
                   server=server, counter=counter)


def test_rehearsal_end_to_end(rig):
    cell = rig[0]
    res = run(rig)
    assert list(res)[-1] == "checks"
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    want = {m["name"] for m in harness.cell_metrics(cell, "end_to_end")}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    c = res["checks"]
    assert c["max_abs_err"]["value"] <= c["max_abs_err"]["limit"]
    assert c["compiles_in_window"]["value"] == 0
    assert c["unserved_requests"]["value"] == 0
    assert c["interpret_dispatches"]["value"] > 0      # CPU: interpret
    assert res["correct"] is False and res["failed"] == 0
    assert res["attempted"] > 0


def test_rehearsal_traced(rig):
    cell = rig[0]
    res = run(rig, trace=True)
    want = {m["name"] for m in harness.cell_metrics(cell, "per_layer")}
    got = set(res["metrics"])
    # no chip: nothing for the device-trace readers to read
    device_only = {m["name"] for m in harness.cell_metrics(cell, "per_layer")
                   if m["name"].split(".")[0] in
                   ("keyswitch_roofline", "ntt_roofline", "device_idle")}
    assert got == want - device_only
    assert "breakdown" in res and "window_s" in res["device"]


def _fault(server, kind):
    eng = server.backend.engine
    attr = "decode_batch" if kind == "altered" else "run_schedule"
    orig = getattr(eng, attr)
    if kind in ("unchanged", "half"):

        def broken(schedule, inputs, *a, **kw):
            outs, stage_s = orig(schedule, inputs, *a, **kw)
            out = np.array(outs[0])
            if kind == "unchanged":       # a step returning its state
                out = np.asarray(inputs[0], dtype=out.dtype).copy()
            else:                         # half of the batch left out
                out[out.shape[0] // 2:] = 0
            return [out] + list(outs[1:]), stage_s
    else:                                 # an answer altered where made
        def broken(cb):
            out = np.array(orig(cb))
            out[0, 0] += 1.0
            return out
    setattr(eng, attr, broken)
    return lambda: setattr(eng, attr, orig)


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_fault_fails_the_check(rig, kind):
    cell, server, _ = rig
    undo = _fault(server, kind)
    try:
        res = run(rig, seed=11)
    finally:
        undo()
    c = res["checks"]["max_abs_err"]
    assert c["value"] > c["limit"]
    assert res["correct"] is False and res["failed"] > 0
