"""Batcher: request slots over padded slots (ciphertexts x slots each),
over the window's batches."""


def read(run):
    bs = run.window.counted
    used = sum(n for b in bs for _, _, n in b.requests)
    padded = sum(b.n_ct for b in bs) * run.params.slots
    return 100.0 * used / padded if padded else None
