"""Distributed-runtime substrate tests: checkpoint/restore + elastic
reshard, async checkpointing, fault supervisor replay, straggler detection,
optimizer, data determinism."""
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.train import checkpoint as ckpt
from repro.train.fault import FailureEvent, StragglerEvent, Supervisor
from repro.train.optim import adamw_init, adamw_update, clip_by_global_norm


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"a": jnp.asarray(rng.normal(size=(8, 16)).astype(np.float32)),
            "b": {"c": jnp.asarray(rng.normal(size=(4,)).astype(np.float32)),
                  "d": jnp.asarray(rng.integers(0, 5, (3, 3)),
                                   dtype=jnp.int32)}}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    ckpt.save_checkpoint(str(tmp_path), 7, t)
    restored, step = ckpt.restore_checkpoint(str(tmp_path), t)
    assert step == 7
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_gc_keeps_latest(tmp_path):
    t = _tree()
    for s in (1, 2, 3, 4, 5):
        ckpt.save_checkpoint(str(tmp_path), s, t, keep=2)
    assert ckpt.all_steps(str(tmp_path)) == [4, 5]


def test_async_checkpointer(tmp_path):
    t = _tree()
    ac = ckpt.AsyncCheckpointer(str(tmp_path))
    ac.save(3, t)
    ac.wait()
    restored, step = ckpt.restore_checkpoint(str(tmp_path), t)
    assert step == 3
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(t["a"]))


def test_elastic_reshard_roundtrip(tmp_path):
    """Save under one mesh, restore under a different mesh (elastic)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    t = _tree()
    ckpt.save_checkpoint(str(tmp_path), 1, t)
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(1, 1)
    shardings = jax.tree.map(
        lambda x: NamedSharding(mesh, P(*([None] * x.ndim))), t)
    restored, _ = ckpt.restore_checkpoint(str(tmp_path), t,
                                          shardings=shardings)
    np.testing.assert_array_equal(np.asarray(restored["b"]["c"]),
                                  np.asarray(t["b"]["c"]))


def test_supervisor_failure_replay(tmp_path):
    """Inject a failure mid-run; supervisor restores and replays to the
    same final state as a failure-free run (deterministic data)."""
    def step_fn(params, opt, batch):
        new_params = jax.tree.map(lambda p: p + batch["x"].mean(), params)
        return new_params, opt, {"loss": batch["x"].mean()}

    def make_batch(step):
        rng = np.random.default_rng(100 + step)
        return {"x": jnp.asarray(rng.normal(size=(4,)).astype(np.float32))}

    params0 = {"w": jnp.zeros((2,))}

    # failure-free reference
    sup_ref = Supervisor(step_fn, str(tmp_path / "ref"), ckpt_every=2)
    (ref_params, _), _ = sup_ref.run((params0, {}), make_batch, 10)

    fired = {"done": False}

    def injector(step):
        if step == 7 and not fired["done"]:
            fired["done"] = True
            raise RuntimeError("simulated device failure")

    sup = Supervisor(step_fn, str(tmp_path / "run"), ckpt_every=2,
                     fail_injector=injector)
    (got_params, _), _ = sup.run((params0, {}), make_batch, 10)
    assert any(isinstance(e, FailureEvent) for e in sup.events)
    np.testing.assert_allclose(np.asarray(got_params["w"]),
                               np.asarray(ref_params["w"]), rtol=1e-6)


def test_supervisor_straggler_detection(tmp_path):
    def step_fn(params, opt, batch):
        if int(batch["i"]) == 6:
            time.sleep(0.3)
        return params, opt, {"loss": jnp.zeros(())}

    sup = Supervisor(step_fn, str(tmp_path), ckpt_every=100,
                     straggler_k=4.0)
    sup.run(({"w": jnp.zeros(1)}, {}), lambda s: {"i": jnp.int32(s)}, 10)
    assert any(isinstance(e, StragglerEvent) for e in sup.events)


def test_adamw_descends_quadratic():
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = adamw_init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}          # d/dw ||w||^2
        grads, gn = clip_by_global_norm(grads, 10.0)
        params, state = adamw_update(params, grads, state, lr=5e-2, wd=0.0)
    assert float(jnp.abs(params["w"]).max()) < 0.2


def test_synthetic_data_deterministic():
    from repro.configs import get_config
    from repro.data.pipeline import SyntheticLMDataset
    cfg = get_config("qwen3-8b", smoke=True)
    ds = SyntheticLMDataset(cfg, batch=2, seq=16)
    b1, b2 = ds.batch_at(5), ds.batch_at(5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = ds.batch_at(6)
    assert (b1["tokens"] != b3["tokens"]).any()


def test_compressed_psum_single_pod():
    """n_pod=1 degenerate case runs on one device; error feedback carries
    the quantization residual."""
    from repro.train.compress import (compressed_pod_mean,
                                      init_error_feedback)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("pod",))
    g = {"w": jnp.asarray(np.linspace(-1, 1, 64,
                                      dtype=np.float32))[None]}  # (1, 64)
    err = init_error_feedback(g)
    mean, new_err = compressed_pod_mean(g, err, mesh)
    # reconstruction + residual == original (exact error feedback identity)
    recon = np.asarray(mean["w"]) + np.asarray(new_err["w"][0])
    np.testing.assert_allclose(recon, np.asarray(g["w"][0]), atol=1e-6)
