"""End-to-end LM pretraining driver: ~100M-param dense transformer,
a few hundred steps, with checkpoint/restart + straggler supervision.

Defaults are CPU-sized (~27M params, 200 steps); pass --full for the
~115M-param variant (same code path, longer wall time).

    PYTHONPATH=src python examples/lm_pretrain.py --steps 200
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse
import time

import jax

from repro.data.pipeline import SyntheticLMDataset, shard_batch
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.models.config import ArchConfig
from repro.train.fault import Supervisor
from repro.train.optim import adamw_init

SMALL = ArchConfig(name="lm-27m", family="dense", n_layers=6, d_model=384,
                   d_ff=1536, vocab=32000, n_heads=6, n_kv_heads=6,
                   head_dim=64, attention="gqa")
FULL = ArchConfig(name="lm-115m", family="dense", n_layers=10, d_model=640,
                  d_ff=2560, vocab=50304, n_heads=10, n_kv_heads=10,
                  head_dim=64, attention="gqa")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/lm_pretrain_ckpt")
    args = ap.parse_args()

    cfg = FULL if args.full else SMALL
    mesh = make_host_mesh()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    n_params = cfg.param_count()
    print(f"{cfg.name}: {n_params/1e6:.1f}M params, "
          f"{args.steps} steps x {args.batch}x{args.seq} tokens")

    opt = adamw_init(params)
    ds = SyntheticLMDataset(cfg, args.batch, args.seq)
    with jax.set_mesh(mesh):
        step_fn = jax.jit(M.make_train_step(cfg, mesh, learning_rate=6e-4))
        sup = Supervisor(step_fn, args.ckpt_dir, ckpt_every=100)
        t0 = time.time()
        (params, opt), hist = sup.run(
            (params, opt), lambda s: shard_batch(ds.batch_at(s), mesh),
            args.steps)
        dt = time.time() - t0
    first = sum(h["loss"] for h in hist[:10]) / 10
    last = sum(h["loss"] for h in hist[-10:]) / 10
    print(f"loss: first10={first:.4f} last10={last:.4f} "
          f"(delta {first-last:+.4f})")
    tput = args.steps * args.batch * args.seq / dt
    print(f"throughput: {tput:.0f} tok/s ({dt:.1f}s total); "
          f"model flops/step ~ {6*n_params*args.batch*args.seq/1e9:.1f} GFLOP")
    assert last < first, "loss did not decrease"


if __name__ == "__main__":
    main()
