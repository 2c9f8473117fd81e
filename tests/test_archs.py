"""Per-architecture smoke tests (assignment requirement): every assigned
arch instantiates a REDUCED same-family config and runs one forward/train
step + one decode step on CPU, asserting output shapes and no NaNs.

The FULL configs are exercised only via the dry-run (ShapeDtypeStruct —
no allocation); see launch/dryrun.py and EXPERIMENTS.md §Dry-run.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config, list_archs
from repro.models import model as M
from repro.train.optim import adamw_init


@pytest.fixture(scope="module")
def mesh():
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh(1, 1)


def _batch(cfg, rng, b=2, s=32):
    out = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)),
                                 jnp.int32),
           "labels": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)),
                                 jnp.int32)}
    if cfg.xattn_period:
        out["images"] = jnp.asarray(
            rng.normal(size=(b, cfg.n_img_tokens, cfg.d_model)), jnp.bfloat16)
    if cfg.enc_dec:
        out["frames"] = jnp.asarray(rng.normal(size=(b, s, cfg.d_model)),
                                    jnp.bfloat16)
    return out


@pytest.mark.parametrize("arch", list_archs())
def test_smoke_forward_and_loss(arch, mesh):
    cfg = get_config(arch, smoke=True)
    rng = np.random.default_rng(0)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, rng)
    with jax.set_mesh(mesh):
        logits, mtp_logits, aux, _ = M.forward(params, cfg, batch, mesh)
        loss, metrics = M.loss_fn(params, cfg, batch, mesh)
    assert logits.shape == (2, 32, cfg.vocab)
    assert np.isfinite(float(loss)), f"{arch}: non-finite loss"
    assert not np.isnan(np.asarray(logits, dtype=np.float32)).any()
    if cfg.mtp:
        assert mtp_logits.shape == (2, 32, cfg.vocab)


@pytest.mark.parametrize("arch", list_archs())
def test_smoke_train_step(arch, mesh):
    cfg = get_config(arch, smoke=True)
    rng = np.random.default_rng(1)
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    opt = adamw_init(params)
    batch = _batch(cfg, rng)
    with jax.set_mesh(mesh):
        step = jax.jit(M.make_train_step(cfg, mesh))
        new_params, new_opt, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually moved
    moved = any(
        not np.array_equal(np.asarray(a, np.float32),
                           np.asarray(b, np.float32))
        for a, b in zip(jax.tree.leaves(params),
                        jax.tree.leaves(new_params)))
    assert moved, f"{arch}: train step did not update params"


@pytest.mark.parametrize("arch", list_archs())
def test_smoke_decode_step(arch, mesh):
    cfg = get_config(arch, smoke=True)
    rng = np.random.default_rng(2)
    params = M.init_params(cfg, jax.random.PRNGKey(2))
    b, s_max = 2, 32
    cache = M.init_cache(cfg, b, s_max)
    if cfg.enc_dec:
        cache["memory"] = jnp.asarray(rng.normal(size=(b, 4096, cfg.d_model)),
                                      jnp.bfloat16)
    if cfg.xattn_period:
        cache["images"] = jnp.asarray(
            rng.normal(size=(b, cfg.n_img_tokens, cfg.d_model)), jnp.bfloat16)
    with jax.set_mesh(mesh):
        serve = jax.jit(M.make_serve_step(cfg, mesh))
        tok = jnp.zeros((b,), jnp.int32)
        for pos in range(3):
            tok, cache = serve(params, cache, tok, jnp.int32(pos))
    assert tok.shape == (b,)
    assert (np.asarray(tok) >= 0).all() and (np.asarray(tok) < cfg.vocab).all()


def test_full_configs_match_assignment():
    """The FULL configs carry the exact published dims from the assignment."""
    expect = {
        "deepseek-v3-671b": dict(n_layers=61, d_model=7168, n_heads=128,
                                 vocab=129280, n_experts=256, top_k=8,
                                 d_ff_expert=2048),
        "arctic-480b": dict(n_layers=35, d_model=7168, n_heads=56,
                            n_kv_heads=8, d_ff=4864, vocab=32000,
                            n_experts=128, top_k=2),
        "llama-3.2-vision-90b": dict(n_layers=100, d_model=8192, n_heads=64,
                                     n_kv_heads=8, d_ff=28672, vocab=128256),
        "seamless-m4t-large-v2": dict(n_layers=24, d_model=1024, n_heads=16,
                                      d_ff=8192, vocab=256206, enc_dec=True),
        "qwen3-8b": dict(n_layers=36, d_model=4096, n_heads=32, n_kv_heads=8,
                         d_ff=12288, vocab=151936, qk_norm=True),
        "granite-3-8b": dict(n_layers=40, d_model=4096, n_heads=32,
                             n_kv_heads=8, d_ff=12800, vocab=49155),
        "codeqwen1.5-7b": dict(n_layers=32, d_model=4096, n_heads=32,
                               n_kv_heads=32, d_ff=13440, vocab=92416),
        "mistral-nemo-12b": dict(n_layers=40, d_model=5120, n_heads=32,
                                 n_kv_heads=8, d_ff=14336, vocab=131072),
        "rwkv6-3b": dict(n_layers=32, d_model=2560, d_ff=8960, vocab=65536),
        "recurrentgemma-2b": dict(n_layers=26, d_model=2560, n_heads=10,
                                  n_kv_heads=1, d_ff=7680, vocab=256000),
    }
    for name, fields in expect.items():
        cfg = get_config(name)
        for k, v in fields.items():
            assert getattr(cfg, k) == v, (name, k, getattr(cfg, k), v)


def test_long_500k_applicability():
    from repro.launch.specs import cell_applicable
    for arch in list_archs():
        cfg = get_config(arch)
        ok, _ = cell_applicable(cfg, "long_500k")
        if arch in ("rwkv6_3b", "recurrentgemma_2b"):
            assert ok, f"{arch} should run long_500k"
        else:
            assert not ok, f"{arch} should skip long_500k"
