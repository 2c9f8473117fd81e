"""JAX's persistent compilation cache for the serving entry points.

A cold process at the paper's parameters compiles dozens of XLA
programs; the persistent cache lets a later process (and identical
programs within one process) skip them. The directory is
``$JAX_COMPILATION_CACHE_DIR`` when that is set, and otherwise a fixed,
git-ignored ``.jax_cache`` at the root of the checkout — never a path
built from a temporary name, a pid or the time, because the path is part
of what makes an entry found again.

Called at the start of ``serve_fhe.main`` and ``chip_smoke.py``, never at
import. On the CPU backend the cache stays off.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> str:
    """Where the persistent compilation cache lives."""
    return os.environ.get(ENV) or str(REPO_CACHE)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``cache_dir()`` when
    JAX runs on an accelerator; returns the directory, or "" for off.

    On the CPU backend it stays off: CPU compiles are cheap, and
    XLA:CPU entries reload with machine-feature warnings."""
    import jax
    if jax.default_backend() == "cpu":
        return ""
    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
