"""Compile the served Pallas kernels for a described TPU v5e.

Interpret mode (every other kernel test) cannot see what the TPU's
compiler refuses: block shapes off the (8, 128) tiling, i64 index maps,
more VMEM than a kernel may use. These tests lower and compile the
kernels of the encrypted serving path at the paper's parameters
(`paper_params_bootstrap`: logN=16, L=23, dnum=4) for one chip of a
described ``v5e:2x2`` — no chip is needed, and nothing runs.

The topology is described only inside the module-scoped fixture below,
never while a module is imported: the TPU library admits one process at
a time, and the fixture skips where it cannot be loaded.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.params import paper_params_bootstrap

LEVEL = 20          # serve_fhe's start level at paper parameters
BATCH = 2


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def paper_ctx():
    from repro.core.context import CkksContext
    return CkksContext(paper_params_bootstrap())


@pytest.fixture(scope="module")
def paper_tables(paper_ctx):
    from repro.kernels.limb_ntt import context_tables
    return context_tables(paper_ctx)


def _spec(x, sharding):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def _compiled_text(fn, args, sharding) -> str:
    specs = jax.tree_util.tree_map(lambda a: _spec(a, sharding), args)
    return jax.jit(fn).lower(*specs).compile().as_text()


def test_fused_keyswitch_compiles_for_v5e(one_chip, paper_ctx, paper_tables):
    from repro.kernels.keyswitch import FusedKeySwitch
    ctx = paper_ctx
    fks = FusedKeySwitch(ctx, paper_tables)
    run = fks._build(BATCH, LEVEL, itp=False)
    tabs = fks._tables(LEVEL)
    d2 = jax.ShapeDtypeStruct((BATCH, LEVEL + 1, ctx.n), jnp.uint64)
    ksk_m = jax.ShapeDtypeStruct((ctx.params.dnum, 2, len(ctx.primes),
                                  ctx.n // 128, 128), jnp.uint32)
    text = _compiled_text(run, (d2, ksk_m, paper_tables, tabs.arrays),
                          one_chip)
    assert text.count("tpu_custom_call") >= FusedKeySwitch.DISPATCHES_PER_APPLY


def test_modmul_compiles_for_v5e(one_chip):
    from repro.kernels.modmul import modmul_pallas
    params = paper_params_bootstrap()
    rows = BATCH * 2 * (params.n_levels + 1)
    a = jax.ShapeDtypeStruct((rows, params.n), jnp.uint32)
    q = jax.ShapeDtypeStruct((rows,), jnp.uint32)

    def fn(a, b, q, qi):
        return modmul_pallas(a, b, q, qi, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, (a, a, q, q), one_chip)


@pytest.mark.parametrize("inverse", [False, True], ids=["ntt", "intt"])
def test_limb_ntt_compiles_for_v5e(one_chip, paper_ctx, paper_tables,
                                  inverse):
    from repro.kernels.limb_ntt import ntt_pallas, tile_shape
    ctx = paper_ctx
    x = jax.ShapeDtypeStruct((2, LEVEL + 1) + tile_shape(ctx.n), jnp.uint32)
    ids = jnp.arange(LEVEL + 1, dtype=jnp.int32)

    def fn(x, tabs, ids):
        return ntt_pallas(x, tabs, ids, inverse=inverse, interpret=False)
    text = _compiled_text(fn, (x, paper_tables, ids), one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("lazy", [False, True], ids=["eager", "lazy"])
def test_bconv_compiles_for_v5e(one_chip, lazy):
    """Off the served path (fig14's staged baseline), kept compilable."""
    from repro.kernels.bconv import bconv_pallas
    params = paper_params_bootstrap()
    alpha, dst = params.alpha, LEVEL + 1 + params.n_special
    v = jax.ShapeDtypeStruct((alpha, params.n), jnp.uint32)
    w = jax.ShapeDtypeStruct((dst, alpha), jnp.uint32)
    p = jax.ShapeDtypeStruct((dst,), jnp.uint32)

    def fn(v, w, p, pi):
        return bconv_pallas(v, w, p, pi, lazy=lazy, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, (v, w, p, p), one_chip)
