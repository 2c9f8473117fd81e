"""Host runtime: the program's ``gc`` records (generation-1 and
generation-2 garbage-collection pauses, counted by the listener every
``CiphertextBackend`` installs), mean ms per batch; 0 where none fell
inside a batch."""
from bench.ring import per_batch_ms


def read(run):
    return per_batch_ms(run, "gc")
