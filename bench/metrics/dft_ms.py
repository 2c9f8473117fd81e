"""Homomorphic op: the bootstrap's factored DFTs, the program's ``cts``
(CoeffToSlot and the real/imaginary split) and ``stc`` (the split undone
and SlotToCoeff) records, mean ms per batch."""
from bench.ring import per_batch_ms


def read(run):
    cts, stc = per_batch_ms(run, "cts"), per_batch_ms(run, "stc")
    if cts is None or stc is None:
        return None
    return cts + stc
