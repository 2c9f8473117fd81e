"""Host egress, inline check: the program's ``check`` spans
(``CiphertextBackend.execute`` comparing every output with its plaintext
oracle, ``reference_eval``, after the returned service seconds), mean ms
per batch."""
from bench.ring import per_batch_ms


def read(run):
    return per_batch_ms(run, "check")
