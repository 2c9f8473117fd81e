"""The limb NTT (kernels/limb_ntt.py, ``_ntt_kernel``): every NTT on the
kernel route outside the fused keyswitch. The trace names its launches
after the jitted ``_ntt_limbs``, or ``vmap_jit__ntt_limbs__`` where a
batched applier maps it: ``OPS``.

Compulsory HBM bytes of one launch over ``limbs`` limb rows (batch x
limbs) of N words: each row read once and written once. The twiddle
tables are the same for every call and are not counted.
"""
from bench.roofline import WORD

OPS = ("_ntt_limbs", "vmap_jit__ntt_limbs__")


def bytes_per_call(limbs: int, n: int) -> int:
    return 2 * limbs * n * WORD


def limbs_of_shape(shape, n: int) -> int:
    """Limb rows of a launch from its (B, L, R, C) tile shape."""
    total = 1
    for d in shape:
        total *= d
    return total // n
