"""Homomorphic op: the bootstrap's EvalMod, the program's ``evalmod``
records (Chebyshev ladder and double angles on the real and imaginary
parts together), mean ms per batch."""
from bench.ring import per_batch_ms


def read(run):
    return per_batch_ms(run, "evalmod")
