"""Homomorphic op: the program's ``bootstrap`` records (one per bootstrap
op, ModRaise through SlotToCoeff, device work included), mean ms per
batch."""
from bench.ring import per_batch_ms


def read(run):
    return per_batch_ms(run, "bootstrap")
