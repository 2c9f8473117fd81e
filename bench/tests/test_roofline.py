"""Compulsory bytes worked by hand at the paper's logN=16, level 20, dnum=4
(alpha = ceil(24/4) = 6 special primes, 4 active digits)."""
import pytest

from bench import roofline

N = 1 << 16
ALPHA = 6
MB = 1e6


def test_keyswitch_evk_for_active_digits():
    ks = roofline.load("keyswitch")
    assert ks.active_digits(20, ALPHA) == 4
    # 4 digits x 2 x (21 + 6) limbs x 65536 words x 4 bytes
    assert ks.evk_bytes(20, N, ALPHA) == 4 * 2 * 27 * N * 4 == 56_623_104
    assert ks.evk_bytes(20, N, ALPHA) / MB == pytest.approx(56.6, abs=0.05)
    assert ks.active_digits(5, ALPHA) == 1
    assert ks.active_digits(6, ALPHA) == 2


def test_keyswitch_bytes_per_call():
    ks = roofline.load("keyswitch")
    # batch 2: input 2 x 21 limbs, outputs 2 x 2 x 21 limbs, plus the key
    want = (2 * 21 + 4 * 21) * N * 4 + 56_623_104
    assert ks.bytes_per_call(2, 20, N, ALPHA) == want


def test_limb_ntt_bytes():
    nt = roofline.load("limb_ntt")
    assert nt.limbs_of_shape((2, 21, 512, 128), N) == 42
    assert nt.bytes_per_call(42, N) == 2 * 42 * N * 4


class _Op:
    def __init__(self, idx, kind, args=(), **meta):
        self.idx, self.kind, self.args = idx, kind, tuple(args)
        self.meta = meta
        self.level = None


def test_schedule_bytes_by_hand():
    sched = roofline.load("schedule")
    ct = lambda limbs: 2 * limbs * N * 4          # noqa: E731
    ops = [_Op(0, "input"), _Op(1, "rotate", [0], step=1),
           _Op(2, "hadd", [0, 1]), _Op(3, "pmul", [2], const="d0"),
           _Op(4, "hmul", [3, 3])]
    b = 2
    want = (b * ct(21)                                     # encrypt input
            + b * 2 * ct(21) + 56_623_104                  # rotate + key
            + b * 3 * ct(21)                               # hadd
            + b * ct(21) + 21 * N * 4 + b * ct(20)         # pmul, rescale
            + b * 2 * ct(20) + b * ct(19)                  # hmul
            + 4 * 2 * 26 * N * 4                           # its relin key
            + b * ct(19))                                  # decrypt output
    got = sched.batch_bytes(ops, [0], [4], 20, b, N, N // 2, ALPHA)
    assert got == want


def test_rotation_by_zero_reads_no_key():
    sched = roofline.load("schedule")
    ops = [_Op(0, "input"), _Op(1, "rotate", [0], step=N // 2)]
    ct = 2 * 21 * N * 4
    assert sched.batch_bytes(ops, [0], [1], 20, 1, N, N // 2, ALPHA) == \
        4 * ct


def test_peaks_known_and_unknown_device():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
