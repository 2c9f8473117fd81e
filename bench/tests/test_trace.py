"""Trace reduction on a small recorded trace: one matvec batch at the
paper's logN=16 on a TPU v5e (a slice of a chip run's ``.xplane.pb``: every
Pallas kernel op of the batch, one in twelve of its other ops, and the
harness's host annotations)."""
import os
import types

import pytest

from bench import metrics, roofline
from bench import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "matvec_one_batch.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return tr.reduce_file(DATA)


def test_device_busy_and_window(summary):
    assert summary.n_devices == 1
    # the window is what the host's serve annotation spans
    assert summary.window_s == pytest.approx(1.400576269)
    # one op at a time on the line: busy is the sum of the ops
    assert summary.busy_s == pytest.approx(sum(summary.op_time_s.values()))
    assert summary.busy_s < summary.window_s


def test_kernels_by_launching_function(summary):
    count = {}
    for o in summary.ops:
        if o.kernel:
            count[o.kernel] = count.get(o.kernel, 0) + 1
    # 6 rotations (BSGS of a 16-diagonal matvec), 4 launches each
    assert count["run"] == 24
    assert set(count) == {"run", "_ntt_limbs", "vmap_jit__ntt_limbs__",
                          "_modmul_impl"}
    shapes = {o.shape for o in summary.ops if o.kernel == "_ntt_limbs"}
    assert all(s[-2:] == (512, 128) for s in shapes)


def test_idle_gaps_named_by_host_work(summary):
    name, longest = summary.gaps[0]
    assert name == "decode" and longest > 0.4
    br = summary.breakdown()
    assert br["device_ops"][0][0] == "run [tpu_custom_call]"
    assert len(br["device_ops"]) <= 10 and len(br["idle_gaps"]) <= 10


def _run(summary, ks_calls):
    from repro.core.params import CkksParams
    params = CkksParams(log_n=16, log_scale=28, n_levels=23, dnum=4,
                        first_mod_bits=31, scale_mod_bits=28,
                        special_mod_bits=31)
    w = types.SimpleNamespace(trace=summary, ks_calls=ks_calls)
    return types.SimpleNamespace(window=w, params=params,
                                 peaks=roofline.peaks("TPU v5 lite"))


def test_keyswitch_roofline_by_hand(summary):
    calls = [(2, 20)] * 3 + [(2, 19)] * 3
    got = metrics.reader("keyswitch_roofline.steady")(_run(summary, calls))
    ks = roofline.load("keyswitch")
    n = 1 << 16
    need = 3 * ks.bytes_per_call(2, 20, n, 6) + \
        3 * ks.bytes_per_call(2, 19, n, 6)
    want = 100 * need / 819e9 / summary.kernel_time_s["run"]
    assert got == pytest.approx(want)
    assert 0 < got < 100


def test_ntt_roofline_below_peak(summary):
    got = metrics.reader("ntt_roofline.steady")(_run(summary, []))
    assert 0 < got < 100


def test_no_device_plane_reads_nothing(summary):
    empty = tr.Summary(1.0, 0.0, 0, [], {}, {}, [], {})
    run = _run(empty, [(2, 20)])
    assert metrics.reader("device_idle.steady")(run) is None
    assert metrics.reader("keyswitch_roofline.steady")(run) is None
    assert metrics.reader("ntt_roofline.steady")(run) is None
