"""Serving driver: batched prefill + decode with KV cache for any zoo arch.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
        --batch 4 --prompt-len 32 --gen 32
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.models import model as M


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = make_host_mesh()
    rng = np.random.default_rng(0)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    b = args.batch
    s_max = args.prompt_len + args.gen

    with jax.set_mesh(mesh):
        serve = jax.jit(M.make_serve_step(cfg, mesh))
        cache = M.init_cache(cfg, b, s_max)
        if cfg.enc_dec:
            cache["memory"] = jnp.asarray(
                rng.normal(size=(b, 4096, cfg.d_model)), jnp.bfloat16)
        if cfg.xattn_period:
            cache["images"] = jnp.asarray(
                rng.normal(size=(b, cfg.n_img_tokens, cfg.d_model)),
                jnp.bfloat16)
        prompt = rng.integers(0, cfg.vocab, (b, args.prompt_len))
        # prefill by stepping (robust across cache families)
        tok = jnp.asarray(prompt[:, 0], jnp.int32)
        t0 = time.time()
        for i in range(args.prompt_len - 1):
            _, cache = serve(params, cache, jnp.asarray(prompt[:, i],
                                                        jnp.int32),
                             jnp.int32(i))
        outs = []
        tok = jnp.asarray(prompt[:, -1], jnp.int32)
        for i in range(args.gen):
            tok, cache = serve(params, cache, tok,
                               jnp.int32(args.prompt_len - 1 + i))
            outs.append(np.asarray(tok))
        jax.block_until_ready(tok)
        dt = time.time() - t0
        gen = np.stack(outs, axis=1)
        print(f"arch={cfg.name} generated {gen.shape} tokens")
        print(gen[:, :16])
        steps = args.prompt_len - 1 + args.gen
        print(f"{steps} serve steps in {dt:.2f}s -> "
              f"{b * steps / dt:.1f} tok/s (batch={b})")


if __name__ == "__main__":
    main()
