"""Training driver: any zoo arch on the local mesh (or production mesh
under the dry-run device flag), with checkpoint/restart, straggler watch,
and deterministic replay.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --smoke \
        --steps 100 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import os
import time

import jax

from repro.configs import get_config
from repro.data.pipeline import SyntheticLMDataset, shard_batch
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.train.fault import Supervisor
from repro.train.optim import adamw_init


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = make_host_mesh()
    print(f"arch={cfg.name} params~{cfg.param_count()/1e6:.1f}M "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    params = M.init_params(cfg, jax.random.PRNGKey(0))
    opt_state = adamw_init(params)
    ds = SyntheticLMDataset(cfg, args.batch, args.seq)

    with jax.set_mesh(mesh):
        step_fn = jax.jit(M.make_train_step(cfg, mesh,
                                            learning_rate=args.lr))
        start = 0
        if args.resume:
            from repro.train import checkpoint as ckpt
            latest = ckpt.latest_step(args.ckpt_dir)
            if latest:
                tree, start = ckpt.restore_checkpoint(
                    args.ckpt_dir, {"params": params, "opt": opt_state})
                params, opt_state = tree["params"], tree["opt"]
                print(f"resumed from step {start}")

        def make_batch(step):
            return shard_batch(ds.batch_at(step), mesh)

        sup = Supervisor(step_fn, args.ckpt_dir, ckpt_every=args.ckpt_every)
        t0 = time.time()
        (params, opt_state), history = sup.run(
            (params, opt_state), make_batch, args.steps, start_step=start)
        dt = time.time() - t0
        for i, h in enumerate(history):
            if i % args.log_every == 0 or i == len(history) - 1:
                print(f"step {start + i:5d} loss={h['loss']:.4f} "
                      f"ce={h['ce']:.4f} gnorm={h['grad_norm']:.3f}")
        n = max(len(history), 1)
        toks = args.batch * args.seq * n
        print(f"done: {n} steps in {dt:.1f}s "
              f"({toks / dt:.0f} tok/s); events={sup.events}")


if __name__ == "__main__":
    main()
