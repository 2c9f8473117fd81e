"""Host egress, decode: the program's ``decode`` spans
(``CkksEngine.decode_batch``: the batched decrypt and its copy to the
host, then per ciphertext the inverse NTT, the CRT lift and the
embedding), mean ms per batch."""
from bench.ring import per_batch_ms


def read(run):
    return per_batch_ms(run, "decode")
