"""Host ingress per batch: packing the payloads into slot rows
(``CiphertextBackend._pack``) and encoding and encrypting them
(``CkksEngine.encrypt_batch``, to the device's completion)."""
from bench.metrics import per_batch_ms


def read(run):
    return per_batch_ms(run, "ingress_s")
