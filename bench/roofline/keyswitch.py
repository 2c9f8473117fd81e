"""The fused keyswitch (kernels/keyswitch.py): four Pallas launches per
call, ``_intt_scale_kernel`` (twice), ``_bconv_ntt_mulacc_kernel`` and
``_moddown_kernel``, all inside the jitted pipeline ``FusedKeySwitch._build``
returns. The trace names each launch after that function, ``run``
(``%run.<n> = ... custom_call_target="tpu_custom_call"``): ``OPS``.

Compulsory HBM bytes of one call on a batch of ``batch`` polynomials at
``level`` (l+1 = level+1 limbs of N words): the input limbs read once, both
output polynomials written once, and the evaluation key of the active
digits read once, ``digits x 2 x (l+1+alpha) x N`` words (alpha special
primes, digits = ceil((l+1)/alpha)).
"""
from bench.roofline import WORD

OPS = ("run",)


def active_digits(level: int, alpha: int) -> int:
    return -(-(level + 1) // alpha)


def evk_bytes(level: int, n: int, alpha: int) -> int:
    return active_digits(level, alpha) * 2 * (level + 1 + alpha) * n * WORD


def bytes_per_call(batch: int, level: int, n: int, alpha: int) -> int:
    limbs = level + 1
    return (batch * limbs * n * WORD            # input read once
            + 2 * batch * limbs * n * WORD      # two outputs written once
            + evk_bytes(level, n, alpha))
