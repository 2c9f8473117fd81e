"""Compiled stages per batch: the sum of the barrier-timed stage seconds
that ``CkksEngine.run_schedule`` returns."""
from bench.metrics import per_batch_ms


def read(run):
    return per_batch_ms(run, "stages_s")
