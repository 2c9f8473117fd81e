"""Multi-device worker (run in a subprocess with 8 fake CPU devices).

Scenarios exercise the distributed FHE substrate on a real (fake-device)
mesh; the parent test asserts exit status. Keep each scenario exact:
integer FHE math must be bit-identical distributed vs single-device.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def scenario_bconv(variant: str):
    from repro.core.params import test_params
    from repro.core.context import CkksContext
    from repro.core import rns
    from repro.fhe_dist.collective_bconv import (bconv_tables_device,
                                                 distributed_bconv)
    params = test_params(log_n=8, n_levels=7, dnum=2)  # 8 q-limbs
    ctx = CkksContext(params)
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(1, 8)
    src = ctx.q_idx(7)              # 8 limbs -> 1 per device
    dst = ctx.p_idx()               # 8 special? alpha=4 -> pad to 8
    # need |dst| divisible by 8 too: use first 8 q primes as a synthetic dst
    dst = ctx.q_idx(7)
    rng = np.random.default_rng(0)
    v = np.stack([rng.integers(0, ctx.primes[i], size=ctx.n, dtype=np.uint64)
                  for i in src])
    tabs = ctx.bconv_tables(src, dst)
    want = np.asarray(rns.bconv(jnp.asarray(v), tabs))
    qh, sq, w, dq = bconv_tables_device(ctx, src, dst)
    got = np.asarray(distributed_bconv(jnp.asarray(v), qh, sq, w, dq,
                                       mesh, variant=variant))
    assert (got == want).all(), f"distributed bconv ({variant}) mismatch"
    print(f"bconv {variant} exact-match OK")


def scenario_pipeline():
    from repro.fhe_dist.pipeline_exec import run_load_save_pipeline
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("data",))
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(5, 16, 32)).astype(np.float32))
    fns_r1 = [lambda v, k=k: v * (k + 1) for k in range(8)]
    fns_r2 = [lambda v, k=k: v + k for k in range(8)]
    got = run_load_save_pipeline([fns_r1, fns_r2], x, mesh)
    want = x
    for f in fns_r1 + fns_r2:
        want = f(want)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    print("pipeline rounds OK")


def scenario_limb_sharded_hmul():
    """GSPMD limb-sharded HMul == single-device HMul, bit exact."""
    from repro.core.params import CkksParams
    from repro.core.context import CkksContext
    from repro.core.encoder import CkksEncoder
    from repro.core.encryptor import CkksEncryptor
    from repro.core.ciphertext import Plaintext, Ciphertext
    from repro.core import ops
    from jax.sharding import NamedSharding, PartitionSpec as P

    params = CkksParams(log_n=8, log_scale=26, n_levels=7, dnum=2,
                        first_mod_bits=30, scale_mod_bits=26,
                        special_mod_bits=30)
    ctx = CkksContext(params)
    enc = CkksEncoder(ctx)
    encr = CkksEncryptor(ctx, seed=5)
    sk = encr.keygen()
    rk = encr.relin_keygen(sk)
    rng = np.random.default_rng(2)
    s = ctx.n // 2
    v1 = rng.normal(size=s) * 0.3
    v2 = rng.normal(size=s) * 0.3
    scale = 2.0 ** 26
    L = params.n_levels
    ct1 = encr.encrypt_sk(Plaintext(enc.encode(v1, scale, L), L, scale), sk)
    ct2 = encr.encrypt_sk(Plaintext(enc.encode(v2, scale, L), L, scale), sk)
    want = np.asarray(ops.hmul(ctx, ct1, ct2, rk).data)

    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(1, 8)
    limb = NamedSharding(mesh, P(None, "model", None))
    with jax.set_mesh(mesh):
        d1 = jax.device_put(ct1.data, limb)
        d2 = jax.device_put(ct2.data, limb)
        out = ops.hmul(ctx, Ciphertext(d1, L, scale),
                       Ciphertext(d2, L, scale), rk)
        got = np.asarray(out.data)
    assert (got == want).all(), "limb-sharded hmul mismatch"
    print("limb-sharded hmul exact-match OK")


if __name__ == "__main__":
    scen = sys.argv[1]
    if scen == "bconv_ring":
        scenario_bconv("ring")
    elif scen == "bconv_allgather":
        scenario_bconv("allgather")
    elif scen == "pipeline":
        scenario_pipeline()
    elif scen == "hmul":
        scenario_limb_sharded_hmul()
    else:
        raise SystemExit(f"unknown scenario {scen}")
    print("WORKER_OK")
