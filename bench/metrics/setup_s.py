"""Set-up: process start to window start (JAX start-up, context and NTT
tables, key generation, programs compiled or loaded from the cache,
warm-up)."""


def read(run):
    return run.setup_s
