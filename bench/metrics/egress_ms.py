"""Host egress per batch: decrypt and decode (``CkksEngine.decode_batch``)
plus what ``execute`` does after its returned service seconds (the inline
decrypt check against the program's oracle)."""
from bench.metrics import per_batch_ms


def read(run):
    return per_batch_ms(run, "egress_s")
