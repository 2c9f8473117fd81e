"""Production mesh construction.

A FUNCTION (not module-level constant) so importing never touches jax
device state. Single pod: 16x16 = 256 chips (data, model). Multi-pod:
2 pods x 256 = 512 chips with a leading `pod` axis (the slow/DCN axis —
grad-compression and pure-DP only cross it).
"""
from __future__ import annotations

import jax



def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over local devices (smoke tests / examples)."""
    return make_mesh((data, model), ("data", "model"))


def make_mesh(shape, axes):
    """jax.make_mesh with every axis Auto-sharded."""
    return jax.make_mesh(shape, axes, axis_types=(
        jax.sharding.AxisType.Auto,) * len(axes))
