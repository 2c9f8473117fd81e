"""Host egress, CRT lift: the program's ``lift`` spans (per ciphertext,
``rns.crt_lift_centered`` into Python integers and their conversion to
floats, inside ``decode``), mean ms per batch."""
from bench.ring import per_batch_ms


def read(run):
    return per_batch_ms(run, "lift")
