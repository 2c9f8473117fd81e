"""ArchConfig-driven model assembly: parameter schemas (with logical
sharding axes), forward passes (train/prefill), stateful decode, and
jit-able step builders for every assigned architecture family.

Layer stacks are `lax.scan`-ned over stacked parameters (compile-time sane
at 61-100 layers) with `jax.checkpoint` on block bodies (activation remat).
Heterogeneous stacks use scanned super-blocks plus explicit tail layers
(e.g. recurrentgemma's 26 = 8 x [rec,rec,attn] + [rec,rec]).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import recurrent as rec
from repro.models.config import ArchConfig
from repro.models.layers import cross_attention, rmsnorm, swiglu
from repro.sharding.rules import default_rules, spec_for_shape

F32 = jnp.float32

# When True, layer-stack scans are fully unrolled. Used by the roofline
# driver: XLA cost_analysis does not scale while-loop bodies by trip count,
# so rooflines are measured on unrolled reduced-depth configs and
# extrapolated (launch/roofline.py). Never enable for full-depth configs.
SCAN_UNROLL = False

# Remat policy for scanned blocks. 'dots' saves matmul outputs (no fwd
# recompute in backward — EXPERIMENTS.md §Perf iteration 3); 'full'
# recomputes everything (minimum memory).
REMAT_POLICY = "full"


def _remat(f):
    if REMAT_POLICY == "dots":
        return jax.checkpoint(
            f, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(f)


def _scan(f, init, xs):
    return jax.lax.scan(f, init, xs, unroll=True if SCAN_UNROLL else 1)


# ---------------------------------------------------------------------------
# parameter schema
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PSpec:
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    scale: float = 0.02


def _dense_mlp_schema(cfg, d_ff):
    d = cfg.d_model
    return {
        "w_gate": PSpec((d, d_ff), ("embed", "mlp")),
        "w_up": PSpec((d, d_ff), ("embed", "mlp")),
        "w_down": PSpec((d_ff, d), ("mlp", "embed")),
    }


def _gqa_schema(cfg):
    d, h, hkv, dh = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                     cfg.resolved_head_dim)
    s = {
        "wq": PSpec((d, h, dh), ("embed", "heads", "head_dim")),
        "wk": PSpec((d, hkv, dh), ("embed", "kv_heads", "head_dim")),
        "wv": PSpec((d, hkv, dh), ("embed", "kv_heads", "head_dim")),
        "wo": PSpec((h * dh, d), ("mlp", "embed")),
    }
    if cfg.qk_norm:
        s["q_norm"] = PSpec((dh,), ("embed_repl",), 1.0)
        s["k_norm"] = PSpec((dh,), ("embed_repl",), 1.0)
    return s


def _mla_schema(cfg):
    d, h = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq_a": PSpec((d, cfg.q_lora_rank), ("embed", "q_lora")),
        "q_norm": PSpec((cfg.q_lora_rank,), ("embed_repl",), 1.0),
        "wq_b": PSpec((cfg.q_lora_rank, h, dn + dr),
                      (None, "heads", "head_dim")),
        "wkv_a": PSpec((d, cfg.kv_lora_rank + dr), ("embed", None)),
        "kv_norm": PSpec((cfg.kv_lora_rank,), ("embed_repl",), 1.0),
        "wkv_b": PSpec((cfg.kv_lora_rank, h * (dn + dv)), (None, "mlp")),
        "wo": PSpec((h * dv, d), ("mlp", "embed")),
    }


def _moe_schema(cfg):
    d, e, fe = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    s = {
        "w_router": PSpec((d, e), ("embed", None)),
        "w_gate": PSpec((e, d, fe), ("experts", "embed", None)),
        "w_up": PSpec((e, d, fe), ("experts", "embed", None)),
        "w_down": PSpec((e, fe, d), ("experts", None, "embed")),
    }
    if cfg.n_shared_experts:
        s["shared"] = _dense_mlp_schema(cfg, cfg.d_ff_expert * cfg.n_shared_experts)
    if cfg.dense_residual:
        s["dense"] = _dense_mlp_schema(cfg, cfg.d_ff)
    return s


def _rwkv_schema(cfg):
    d = cfg.d_model
    lora_r = 64
    h = d // rec.RWKV_HEAD_DIM
    tm = {
        **{f"mu_{n}": PSpec((d,), ("embed_repl",), 0.5)
           for n in ("r", "k", "v", "g", "w")},
        "wr": PSpec((d, d), ("embed", "mlp")),
        "wk": PSpec((d, d), ("embed", "mlp")),
        "wv": PSpec((d, d), ("embed", "mlp")),
        "wg": PSpec((d, d), ("embed", "mlp")),
        "wo": PSpec((d, d), ("mlp", "embed")),
        "w_lora_a": PSpec((d, lora_r), ("embed", None)),
        "w_lora_b": PSpec((lora_r, d), (None, "embed")),
        "w0": PSpec((d,), ("embed_repl",), 0.5),
        "u_bonus": PSpec((d,), ("embed_repl",), 0.5),
        "ln_x_w": PSpec((d,), ("embed_repl",), 1.0),
    }
    cm = {
        "mu_ck": PSpec((d,), ("embed_repl",), 0.5),
        "mu_cr": PSpec((d,), ("embed_repl",), 0.5),
        "w_key": PSpec((d, cfg.d_ff), ("embed", "mlp")),
        "w_value": PSpec((cfg.d_ff, d), ("mlp", "embed")),
        "w_recept": PSpec((d, d), ("embed", "mlp")),
    }
    return {"ln1": PSpec((d,), ("embed_repl",), 1.0), "time_mix": tm,
            "ln2": PSpec((d,), ("embed_repl",), 1.0), "channel_mix": cm}


def _rglru_schema(cfg):
    d, w = cfg.d_model, cfg.lru_width or cfg.d_model
    return {
        "w_in_y": PSpec((d, w), ("embed", "mlp")),
        "w_in_g": PSpec((d, w), ("embed", "mlp")),
        "conv_w": PSpec((cfg.conv_width, w), ("conv", "mlp"), 0.1),
        "w_a": PSpec((w,), ("embed_repl",), 0.1),
        "b_a": PSpec((w,), ("embed_repl",), 0.1),
        "w_x": PSpec((w,), ("embed_repl",), 0.1),
        "b_x": PSpec((w,), ("embed_repl",), 0.1),
        "lambda_p": PSpec((w,), ("embed_repl",), 0.5),
        "w_out": PSpec((w, d), ("mlp", "embed")),
    }


def _xattn_schema(cfg):
    d, h, dh = cfg.d_model, cfg.n_heads, cfg.resolved_head_dim
    hkv = max(cfg.n_kv_heads, 1)
    return {
        "wq": PSpec((d, h, dh), ("embed", "heads", "head_dim")),
        "wk": PSpec((d, hkv, dh), ("embed", "kv_heads", "head_dim")),
        "wv": PSpec((d, hkv, dh), ("embed", "kv_heads", "head_dim")),
        "wo": PSpec((h * dh, d), ("mlp", "embed")),
        "gate": PSpec((1,), ("embed_repl",), 0.0),
    }


def _block_schema(cfg, kind: str):
    d = cfg.d_model
    base = {"attn_norm": PSpec((d,), ("embed_repl",), 1.0),
            "mlp_norm": PSpec((d,), ("embed_repl",), 1.0)}
    if kind == "dense":
        base["attn"] = (_mla_schema(cfg) if cfg.attention == "mla"
                        else _gqa_schema(cfg))
        ff = 18432 if (cfg.name.startswith("deepseek")) else cfg.d_ff
        base["mlp"] = _dense_mlp_schema(cfg, ff)
    elif kind == "moe":
        base["attn"] = (_mla_schema(cfg) if cfg.attention == "mla"
                        else _gqa_schema(cfg))
        base["moe"] = _moe_schema(cfg)
    elif kind == "xattn":
        base["attn"] = _xattn_schema(cfg)
        base["mlp"] = _dense_mlp_schema(cfg, cfg.d_ff)
    elif kind == "rwkv":
        return _rwkv_schema(cfg)
    elif kind == "rglru":
        base["attn"] = _rglru_schema(cfg)
        base["mlp"] = _dense_mlp_schema(cfg, cfg.d_ff)
    elif kind == "attn":   # recurrentgemma local-attention layer
        base["attn"] = _gqa_schema(cfg)
        base["mlp"] = _dense_mlp_schema(cfg, cfg.d_ff)
    else:
        raise ValueError(kind)
    return base


def _stack(schema, n: int):
    """Add a leading layer axis to every PSpec in a schema subtree."""
    def f(ps: PSpec):
        return PSpec((n,) + ps.shape, ("layers",) + ps.logical, ps.scale)
    return jax.tree.map(f, schema,
                        is_leaf=lambda x: isinstance(x, PSpec))


def param_schema(cfg: ArchConfig) -> Dict[str, Any]:
    d, v = cfg.d_model, cfg.vocab
    s: Dict[str, Any] = {
        "embed": PSpec((v, d), ("vocab", "embed")),
        "final_norm": PSpec((d,), ("embed_repl",), 1.0),
    }
    if not cfg.tie_embeddings:
        s["lm_head"] = PSpec((d, v), ("embed", "vocab"))
    if cfg.enc_dec:
        s["enc_blocks"] = _stack(_block_schema(cfg, "dense"), cfg.n_enc_layers)
        dec = _block_schema(cfg, "dense")
        dec["xattn"] = _xattn_schema(cfg)
        dec["xattn_norm"] = PSpec((d,), ("embed_repl",), 1.0)
        s["dec_blocks"] = _stack(dec, cfg.n_layers)
        s["enc_final_norm"] = PSpec((d,), ("embed_repl",), 1.0)
    elif cfg.xattn_period:
        n_super = cfg.n_layers // (cfg.xattn_period + 1)
        sb = {"self": _stack(_block_schema(cfg, "dense"), cfg.xattn_period),
              "cross": _block_schema(cfg, "xattn")}
        s["superblocks"] = _stack(sb, n_super)
    elif cfg.rwkv:
        s["blocks"] = _stack(_block_schema(cfg, "rwkv"), cfg.n_layers)
    elif cfg.rglru:
        pat = cfg.block_pattern or ("rglru", "rglru", "attn")
        n_super = cfg.n_layers // len(pat)
        tail = cfg.n_layers - n_super * len(pat)
        sb = {f"l{i}_{k}": _block_schema(cfg, k) for i, k in enumerate(pat)}
        s["superblocks"] = _stack(sb, n_super)
        for i in range(tail):
            s[f"tail_{i}"] = _block_schema(cfg, pat[i])
    elif cfg.n_experts:
        if cfg.first_k_dense:
            s["dense_blocks"] = _stack(_block_schema(cfg, "dense"),
                                       cfg.first_k_dense)
        s["moe_blocks"] = _stack(_block_schema(cfg, "moe"),
                                 cfg.n_layers - cfg.first_k_dense)
    else:
        s["blocks"] = _stack(_block_schema(cfg, "dense"), cfg.n_layers)
    if cfg.mtp:
        s["mtp_block"] = _block_schema(cfg, "dense")
        s["mtp_norm"] = PSpec((d,), ("embed_repl",), 1.0)
    return s


def _is_pspec(x):
    return isinstance(x, PSpec)


def abstract_params(cfg: ArchConfig):
    dt = jnp.dtype(cfg.dtype)
    return jax.tree.map(lambda ps: jax.ShapeDtypeStruct(ps.shape, dt),
                        param_schema(cfg), is_leaf=_is_pspec)


def logical_axes(cfg: ArchConfig):
    return jax.tree.map(lambda ps: ps.logical, param_schema(cfg),
                        is_leaf=_is_pspec)


def param_specs(cfg: ArchConfig, mesh: Mesh, rules=None):
    rules = rules or default_rules()
    return jax.tree.map(
        lambda ps: NamedSharding(
            mesh, spec_for_shape(mesh, ps.logical, ps.shape, rules)),
        param_schema(cfg), is_leaf=_is_pspec)


def init_params(cfg: ArchConfig, key):
    """Concrete random init (smoke tests / examples)."""
    dt = jnp.dtype(cfg.dtype)
    schema = param_schema(cfg)
    leaves, treedef = jax.tree.flatten(schema, is_leaf=_is_pspec)
    keys = jax.random.split(key, len(leaves))
    out = []
    for ps, k in zip(leaves, keys):
        if ps.scale == 1.0 and len(ps.shape) <= 2:   # norm weights
            out.append(jnp.ones(ps.shape, dt))
        else:
            out.append(jax.random.normal(k, ps.shape, dt) * ps.scale)
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# forward (training / prefill)
# ---------------------------------------------------------------------------

def _self_attn(x, bp, cfg, positions):
    h = rmsnorm(x, bp["attn_norm"], cfg.norm_eps)
    if cfg.attention == "mla":
        o, kv = attn.mla_forward(h, bp["attn"], cfg, positions)
    else:
        o, kv = attn.gqa_forward(h, bp["attn"], cfg, positions)
    return x + o, kv


def _mlp(x, bp, cfg, d_ff=None):
    h = rmsnorm(x, bp["mlp_norm"], cfg.norm_eps)
    m = bp["mlp"]
    return x + swiglu(h, m["w_gate"], m["w_up"], m["w_down"])


def _moe_layer(x, bp, cfg, mesh, variant="auto"):
    """x (B,S,D) -> (B,S,D), aux. Chooses all_to_all when tokens split
    evenly over the model axis, else the psum schedule."""
    b, s, d = x.shape
    h = rmsnorm(x, bp["mlp_norm"], cfg.norm_eps)
    tokens = h.reshape(b * s, d)
    m = bp["moe"]
    model_n = mesh.shape["model"]
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_n = int(np.prod([mesh.shape[a] for a in dp_axes]))
    use_a2a = (variant == "a2a" or
               (variant == "auto" and (b * s) % (dp_n * model_n) == 0
                and (b * s) // (dp_n * model_n) >= 8))
    wspec = (P("model", None, None),) * 3
    if use_a2a:
        body = partial(moe_mod.moe_all_to_all, cfg=cfg)
        tok_spec = P((*dp_axes, "model"), None)
    else:
        body = partial(moe_mod.moe_psum, cfg=cfg)
        tok_spec = P(dp_axes, None)
    mapped = jax.shard_map(
        lambda t, wr, wg, wu, wd: body(
            t, {"w_router": wr, "w_gate": wg, "w_up": wu, "w_down": wd}),
        mesh=mesh, in_specs=(tok_spec, P(None, None)) + wspec,
        out_specs=(tok_spec, P()), check_vma=False)
    out, aux = mapped(tokens, m["w_router"], m["w_gate"], m["w_up"],
                      m["w_down"])
    aux = jnp.mean(aux)
    out = out.reshape(b, s, d)
    if cfg.n_shared_experts:
        sh = m["shared"]
        out = out + swiglu(h, sh["w_gate"], sh["w_up"], sh["w_down"])
    if cfg.dense_residual:
        dn = m["dense"]
        out = out + swiglu(h, dn["w_gate"], dn["w_up"], dn["w_down"])
    return x + out, aux


def _batch_constraint(x, mesh):
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(dp, *([None] * (x.ndim - 1)))))


def forward(params, cfg: ArchConfig, batch: Dict[str, jnp.ndarray],
            mesh: Mesh, collect_cache: bool = False):
    """Returns (logits, aux_losses, cache_or_None).

    batch: tokens (B,S) [+ images (B,Timg,D) | frames (B,Senc,D)].
    """
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))
    x = _batch_constraint(x, mesh)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    aux_total = jnp.zeros((), F32)
    caches: Dict[str, Any] = {}

    def dense_block(x, bp):
        x, kv = _self_attn(x, bp, cfg, positions)
        x = _mlp(x, bp, cfg)
        return _batch_constraint(x, mesh), kv

    if cfg.enc_dec:
        frames = batch["frames"].astype(x.dtype)
        enc_x = _batch_constraint(frames, mesh)
        enc_pos = jnp.broadcast_to(
            jnp.arange(frames.shape[1], dtype=jnp.int32),
            frames.shape[:2])

        def enc_block(h, bp):
            hn = rmsnorm(h, bp["attn_norm"], cfg.norm_eps)
            o, _ = attn.gqa_forward(hn, bp["attn"], cfg, enc_pos)
            h = h + o
            return _mlp(h, bp, cfg), None

        enc_x, _ = _scan(
            lambda h, bp: _remat(enc_block)(h, bp),
            enc_x, params["enc_blocks"])
        memory = rmsnorm(enc_x, params["enc_final_norm"], cfg.norm_eps)

        def dec_block(h, bp):
            h, kv = _self_attn(h, bp, cfg, positions)
            hx = rmsnorm(h, bp["xattn_norm"], cfg.norm_eps)
            g = jnp.tanh(bp["xattn"]["gate"].astype(F32)).astype(h.dtype)
            h = h + g * cross_attention(hx, memory, bp["xattn"], cfg)
            return _mlp(h, bp, cfg), kv

        x, kvs = _scan(
            lambda h, bp: _remat(dec_block)(h, bp),
            x, params["dec_blocks"])
        if collect_cache:
            caches = {"self_kv": kvs, "memory": memory}

    elif cfg.xattn_period:
        images = batch["images"].astype(x.dtype)

        def superblock(h, sbp):
            h, kvs = _scan(
                lambda hh, bp: _remat(dense_block)(hh, bp),
                h, sbp["self"])
            cb = sbp["cross"]
            hn = rmsnorm(h, cb["attn_norm"], cfg.norm_eps)
            g = jnp.tanh(cb["attn"]["gate"].astype(F32)).astype(h.dtype)
            h = h + g * cross_attention(hn, images, cb["attn"], cfg)
            h = h + swiglu(rmsnorm(h, cb["mlp_norm"], cfg.norm_eps),
                           cb["mlp"]["w_gate"], cb["mlp"]["w_up"],
                           cb["mlp"]["w_down"])
            return _batch_constraint(h, mesh), kvs

        x, kvs = _scan(superblock, x, params["superblocks"])
        if collect_cache:
            caches = {"self_kv": kvs, "images": images}

    elif cfg.rwkv:
        def rwkv_block(h, bp):
            o, (st, xl) = rec.rwkv_time_mix(
                rmsnorm(h, bp["ln1"], cfg.norm_eps), bp["time_mix"], cfg)
            h = h + o
            o, xl2 = rec.rwkv_channel_mix(
                rmsnorm(h, bp["ln2"], cfg.norm_eps), bp["channel_mix"], cfg)
            return h + o, (st, xl, xl2)

        x, states = _scan(
            lambda h, bp: _remat(rwkv_block)(h, bp),
            x, params["blocks"])
        if collect_cache:
            caches = {"states": states}

    elif cfg.rglru:
        pat = cfg.block_pattern or ("rglru", "rglru", "attn")

        def one_layer(h, bp, kind):
            if kind == "rglru":
                hn = rmsnorm(h, bp["attn_norm"], cfg.norm_eps)
                o, st = rec.rglru_block(hn, bp["attn"], cfg)
                h = h + o
                return _mlp(h, bp, cfg), st
            h, kv = _self_attn(h, bp, cfg, positions)
            return _mlp(h, bp, cfg), kv

        def superblock(h, sbp):
            sts = []
            for i, kind in enumerate(pat):
                h, st = _remat(partial(one_layer, kind=kind))(
                    h, sbp[f"l{i}_{kind}"])
                sts.append(st)
            return _batch_constraint(h, mesh), tuple(sts)

        x, states = _scan(superblock, x, params["superblocks"])
        tail_states = []
        n_super = cfg.n_layers // len(pat)
        for i in range(cfg.n_layers - n_super * len(pat)):
            x, st = one_layer(x, params[f"tail_{i}"], pat[i])
            tail_states.append(st)
        if collect_cache:
            caches = {"states": states, "tail_states": tuple(tail_states)}

    elif cfg.n_experts:
        kv_dense = None
        if cfg.first_k_dense:
            x, kv_dense = _scan(
                lambda h, bp: _remat(dense_block)(h, bp),
                x, params["dense_blocks"])

        def moe_block(h, bp):
            h, kv = _self_attn(h, bp, cfg, positions)
            h, aux = _moe_layer(h, bp, cfg, mesh)
            return h, (kv, aux)

        x, (kv_moe, auxes) = _scan(
            lambda h, bp: _remat(moe_block)(h, bp),
            x, params["moe_blocks"])
        aux_total = aux_total + jnp.sum(auxes)
        if collect_cache:
            caches = {"kv_dense": kv_dense, "kv_moe": kv_moe}

    else:
        x, kvs = _scan(
            lambda h, bp: _remat(dense_block)(h, bp),
            x, params["blocks"])
        if collect_cache:
            caches = {"kv": kvs}

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", x, head)

    mtp_logits = None
    if cfg.mtp:
        h2 = rmsnorm(x, params["mtp_norm"], cfg.norm_eps)
        h2, _ = _self_attn(h2, params["mtp_block"], cfg, positions)
        h2 = _mlp(h2, params["mtp_block"], cfg)
        mtp_logits = jnp.einsum("bsd,dv->bsv", h2, head)

    return logits, mtp_logits, aux_total, (caches if collect_cache else None)


# ---------------------------------------------------------------------------
# losses / train step
# ---------------------------------------------------------------------------

def _ce(logits, labels):
    """CE without materializing (B,S,V) f32 log-probs (§Perf iteration 3b):
    gather the label logit first, reduce the logsumexp in f32 on the fly."""
    label_logit = jnp.take_along_axis(
        logits, labels[..., None], axis=-1)[..., 0].astype(F32)
    m = jnp.max(logits, axis=-1).astype(F32)
    lse = m + jnp.log(jnp.sum(
        jnp.exp(logits.astype(F32) - m[..., None]), axis=-1))
    return jnp.mean(lse - label_logit)


def loss_fn(params, cfg: ArchConfig, batch, mesh):
    logits, mtp_logits, aux, _ = forward(params, cfg, batch, mesh)
    labels = batch["labels"]
    loss = _ce(logits, labels)
    metrics = {"ce": loss}
    if cfg.n_experts:
        loss = loss + cfg.router_aux_weight * aux
        metrics["aux"] = aux
    if cfg.mtp and mtp_logits is not None:
        # MTP head predicts token t+2: shift labels one extra step left
        mtp_labels = jnp.concatenate(
            [labels[:, 1:], labels[:, -1:]], axis=1)
        mtp_loss = _ce(mtp_logits[:, :-1], mtp_labels[:, :-1])
        loss = loss + 0.3 * mtp_loss
        metrics["mtp_ce"] = mtp_loss
    metrics["loss"] = loss
    return loss, metrics


def make_train_step(cfg: ArchConfig, mesh: Mesh, learning_rate: float = 3e-4,
                    weight_decay: float = 0.1, grad_clip: float = 1.0):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).
    AdamW with ZeRO-style sharded states (same specs as params)."""
    from repro.train.optim import adamw_update, clip_by_global_norm

    def train_step(params, opt_state, batch):
        grad_fn = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, batch, mesh), has_aux=True)
        (loss, metrics), grads = grad_fn(params)
        grads, gnorm = clip_by_global_norm(grads, grad_clip)
        params, opt_state = adamw_update(params, grads, opt_state,
                                         lr=learning_rate, wd=weight_decay)
        metrics["grad_norm"] = gnorm
        return params, opt_state, metrics

    return train_step


# ---------------------------------------------------------------------------
# caches (decode state) — schemas + zero init
# ---------------------------------------------------------------------------

def cache_schema(cfg: ArchConfig, batch: int, s_max: int) -> Dict[str, Any]:
    """Pytree of PSpec describing the decode cache."""
    dt = cfg.dtype
    hkv, dh = max(cfg.n_kv_heads, 1), cfg.resolved_head_dim
    kv_axes = ("layers", "batch", "kv_heads", "seq", "head_dim")

    def kv(n_layers, s=s_max):
        return {"k": PSpec((n_layers, batch, hkv, s, dh), kv_axes),
                "v": PSpec((n_layers, batch, hkv, s, dh), kv_axes)}

    if cfg.attention == "mla":
        lat = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        mla_axes = ("layers", "batch", "seq", None)
        out = {}
        if cfg.first_k_dense:
            out["dense"] = PSpec((cfg.first_k_dense, batch, s_max, lat),
                                 mla_axes)
        out["moe"] = PSpec((cfg.n_layers - cfg.first_k_dense, batch, s_max,
                            lat), mla_axes)
        return out
    if cfg.enc_dec:
        return {"self": kv(cfg.n_layers),
                "memory": PSpec((batch, 4096, cfg.d_model),
                                ("batch", "seq", "embed_repl"))}
    if cfg.xattn_period:
        n_super = cfg.n_layers // (cfg.xattn_period + 1)
        return {"self": {"k": PSpec((n_super, cfg.xattn_period, batch, hkv,
                                     s_max, dh), ("layers",) + kv_axes),
                         "v": PSpec((n_super, cfg.xattn_period, batch, hkv,
                                     s_max, dh), ("layers",) + kv_axes)},
                "images": PSpec((batch, cfg.n_img_tokens, cfg.d_model),
                                ("batch", "seq", "embed_repl"))}
    if cfg.rwkv:
        h = cfg.d_model // rec.RWKV_HEAD_DIM
        return {"wkv": PSpec((cfg.n_layers, batch, h, rec.RWKV_HEAD_DIM,
                              rec.RWKV_HEAD_DIM),
                             ("layers", "batch", "heads", None, None)),
                "x_tm": PSpec((cfg.n_layers, batch, cfg.d_model),
                              ("layers", "batch", "embed_repl")),
                "x_cm": PSpec((cfg.n_layers, batch, cfg.d_model),
                              ("layers", "batch", "embed_repl"))}
    if cfg.rglru:
        pat = cfg.block_pattern or ("rglru", "rglru", "attn")
        n_super = cfg.n_layers // len(pat)
        w = cfg.lru_width or cfg.d_model
        window = min(cfg.local_window, s_max)
        out = {}
        for i, kind in enumerate(pat):
            if kind == "rglru":
                out[f"conv_{i}"] = PSpec(
                    (n_super, batch, cfg.conv_width - 1, w),
                    ("layers", "batch", None, "mlp"))
                out[f"lru_{i}"] = PSpec((n_super, batch, w),
                                        ("layers", "batch", "mlp"))
            else:
                out[f"k_{i}"] = PSpec((n_super, batch, hkv, window, dh),
                                      kv_axes)
                out[f"v_{i}"] = PSpec((n_super, batch, hkv, window, dh),
                                      kv_axes)
                out[f"pos_{i}"] = PSpec((n_super, window),
                                        ("layers", None))
        # tail layers (pattern prefix)
        tail = cfg.n_layers - n_super * len(pat)
        for i in range(tail):
            if pat[i] == "rglru":
                out[f"tconv_{i}"] = PSpec((batch, cfg.conv_width - 1, w),
                                          ("batch", None, "mlp"))
                out[f"tlru_{i}"] = PSpec((batch, w), ("batch", "mlp"))
            else:
                out[f"tk_{i}"] = PSpec((batch, hkv, window, dh), kv_axes[1:])
                out[f"tv_{i}"] = PSpec((batch, hkv, window, dh), kv_axes[1:])
                out[f"tpos_{i}"] = PSpec((window,), (None,))
        return out
    return kv(cfg.n_layers)


def abstract_cache(cfg: ArchConfig, batch: int, s_max: int):
    def f(ps: PSpec):
        dt = jnp.int32 if "pos" in str(ps.logical) else jnp.dtype(cfg.dtype)
        return jax.ShapeDtypeStruct(ps.shape, dt)
    sch = cache_schema(cfg, batch, s_max)
    out = {}
    for k, v in sch.items():
        if isinstance(v, PSpec):
            dt = jnp.int32 if k.startswith(("pos", "tpos")) else jnp.dtype(cfg.dtype)
            out[k] = jax.ShapeDtypeStruct(v.shape, dt)
        else:
            out[k] = jax.tree.map(
                lambda ps: jax.ShapeDtypeStruct(ps.shape, jnp.dtype(cfg.dtype)),
                v, is_leaf=_is_pspec)
    return out


def cache_specs(cfg: ArchConfig, mesh: Mesh, batch: int, s_max: int,
                rules=None):
    rules = rules or default_rules()
    return jax.tree.map(
        lambda ps: NamedSharding(
            mesh, spec_for_shape(mesh, ps.logical, ps.shape, rules)),
        cache_schema(cfg, batch, s_max), is_leaf=_is_pspec)


def init_cache(cfg: ArchConfig, batch: int, s_max: int):
    ab = abstract_cache(cfg, batch, s_max)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), ab)


# ---------------------------------------------------------------------------
# decode (serve_step)
# ---------------------------------------------------------------------------

def _ring_local_decode(x, bp, cfg, k_cache, v_cache, kv_pos, pos):
    """Sliding-window decode with a ring-buffer cache (window-sized)."""
    from repro.models.layers import (apply_rope, rope_angles)
    import math as _math
    b = x.shape[0]
    h, hkv, dh = cfg.n_heads, max(cfg.n_kv_heads, 1), cfg.resolved_head_dim
    hn = rmsnorm(x, bp["attn_norm"], cfg.norm_eps)
    p = bp["attn"]
    q = jnp.einsum("bsd,dhk->bshk", hn, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", hn, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", hn, p["wv"])
    positions = jnp.full((b, 1), pos, dtype=jnp.int32)
    cos, sin = rope_angles(positions, dh, cfg.rope_theta)
    q = apply_rope(q, cos, sin).transpose(0, 2, 1, 3)
    k = apply_rope(k, cos, sin).transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    window = k_cache.shape[2]
    # int32-uniform indices: x64 mode (FHE core) must not change promotion
    slot = jnp.mod(pos, window).astype(jnp.int32)
    zero = jnp.int32(0)
    k_cache = jax.lax.dynamic_update_slice(k_cache, k, (zero, zero, slot, zero))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v, (zero, zero, slot, zero))
    kv_pos = jax.lax.dynamic_update_slice(kv_pos, pos[None].astype(jnp.int32),
                                          (slot,))
    g, hg = hkv, h // hkv
    qg = q.reshape(b, g, hg, 1, dh)
    s = jnp.einsum("bghqd,bgkd->bghqk", qg, k_cache).astype(F32)
    s = s / _math.sqrt(dh)
    valid = (kv_pos <= pos) & (pos - kv_pos < window) & (kv_pos >= 0)
    s = jnp.where(valid[None, None, None, None, :], s, -1e30)
    pattn = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bghqk,bgkd->bghqd", pattn.astype(v_cache.dtype), v_cache)
    o = o.reshape(b, h, 1, dh).transpose(0, 2, 1, 3).reshape(b, 1, h * dh)
    x = x + jnp.einsum("bse,ed->bsd", o, p["wo"])
    return _mlp(x, bp, cfg), k_cache, v_cache, kv_pos


def decode_forward(params, cfg: ArchConfig, cache, tokens, pos, mesh: Mesh):
    """One decode step. tokens (B,) int32; pos: scalar int32 (current index).
    Returns (logits (B,V), new_cache)."""
    b = tokens.shape[0]
    x = params["embed"][tokens][:, None, :].astype(jnp.dtype(cfg.dtype))
    new_cache = dict(cache)

    def dense_decode(h, bp, kc, vc):
        hn = rmsnorm(h, bp["attn_norm"], cfg.norm_eps)
        o, (kc, vc) = attn.gqa_decode(hn, bp["attn"], cfg, (kc, vc), pos)
        h = h + o
        return _mlp(h, bp, cfg), kc, vc

    if cfg.attention == "mla":
        def mla_dec(h, bp, lat):
            hn = rmsnorm(h, bp["attn_norm"], cfg.norm_eps)
            o, lat = attn.mla_decode(hn, bp["attn"], cfg, lat, pos)
            return h + o, lat

        if cfg.first_k_dense:
            def body_d(h, xs):
                bp, lat = xs
                h, lat = mla_dec(h, bp, lat)
                return _mlp(h, bp, cfg), lat
            x, new_cache["dense"] = _scan(
                body_d, x, (params["dense_blocks"], cache["dense"]))

        def body_m(h, xs):
            bp, lat = xs
            h, lat = mla_dec(h, bp, lat)
            if cfg.n_experts:
                h, _ = _moe_layer(h, bp, cfg, mesh, variant="psum")
            else:
                h = _mlp(h, bp, cfg)
            return h, lat
        blocks_key = "moe_blocks" if cfg.n_experts else "blocks"
        x, new_cache["moe"] = _scan(
            body_m, x, (params[blocks_key], cache["moe"]))

    elif cfg.enc_dec:
        memory = cache["memory"].astype(x.dtype)

        def body(h, xs):
            bp, kc, vc = xs
            h, kc, vc = dense_decode(h, bp, kc, vc)
            hx = rmsnorm(h, bp["xattn_norm"], cfg.norm_eps)
            g = jnp.tanh(bp["xattn"]["gate"].astype(F32)).astype(h.dtype)
            h = h + g * cross_attention(hx, memory, bp["xattn"], cfg)
            return h, (kc, vc)
        x, (ks, vs) = _scan(
            body, x, (params["dec_blocks"], cache["self"]["k"],
                      cache["self"]["v"]))
        new_cache["self"] = {"k": ks, "v": vs}

    elif cfg.xattn_period:
        images = cache["images"].astype(x.dtype)

        def superblock(h, xs):
            sbp, kc, vc = xs
            def inner(hh, ys):
                bp, k1, v1 = ys
                hh, k1, v1 = dense_decode(hh, bp, k1, v1)
                return hh, (k1, v1)
            h, (kc, vc) = _scan(inner, h, (sbp["self"], kc, vc))
            cb = sbp["cross"]
            hn = rmsnorm(h, cb["attn_norm"], cfg.norm_eps)
            g = jnp.tanh(cb["attn"]["gate"].astype(F32)).astype(h.dtype)
            h = h + g * cross_attention(hn, images, cb["attn"], cfg)
            h = h + swiglu(rmsnorm(h, cb["mlp_norm"], cfg.norm_eps),
                           cb["mlp"]["w_gate"], cb["mlp"]["w_up"],
                           cb["mlp"]["w_down"])
            return h, (kc, vc)
        x, (ks, vs) = _scan(
            superblock, x, (params["superblocks"], cache["self"]["k"],
                            cache["self"]["v"]))
        new_cache["self"] = {"k": ks, "v": vs}

    elif cfg.rwkv:
        def body(h, xs):
            bp, st, x_tm, x_cm = xs
            o, (st, x_tm) = rec.rwkv_time_mix(
                rmsnorm(h, bp["ln1"], cfg.norm_eps), bp["time_mix"], cfg,
                state=st, x_last=x_tm)
            h = h + o
            o, x_cm = rec.rwkv_channel_mix(
                rmsnorm(h, bp["ln2"], cfg.norm_eps), bp["channel_mix"], cfg,
                x_last=x_cm)
            return h + o, (st, x_tm[:, -1] if x_tm.ndim == 3 else x_tm, x_cm)
        x, (sts, xtms, xcms) = _scan(
            body, x, (params["blocks"], cache["wkv"].astype(F32),
                      cache["x_tm"], cache["x_cm"]))
        new_cache.update({"wkv": sts.astype(jnp.dtype(cfg.dtype)),
                          "x_tm": xtms, "x_cm": xcms})

    elif cfg.rglru:
        pat = cfg.block_pattern or ("rglru", "rglru", "attn")
        n_super = cfg.n_layers // len(pat)

        def superblock(h, xs):
            sbp = xs[0]
            new_st = []
            for i, kind in enumerate(pat):
                bp = sbp[f"l{i}_{kind}"]
                if kind == "rglru":
                    conv_st, lru_st = xs[1][f"conv_{i}"], xs[1][f"lru_{i}"]
                    hn = rmsnorm(h, bp["attn_norm"], cfg.norm_eps)
                    o, (conv_st, lru_st) = rec.rglru_block(
                        hn, bp["attn"], cfg,
                        state=(conv_st, lru_st.astype(F32)))
                    h = h + o
                    h = _mlp(h, bp, cfg)
                    new_st.append((f"conv_{i}", conv_st))
                    new_st.append((f"lru_{i}",
                                   lru_st.astype(jnp.dtype(cfg.dtype))))
                else:
                    h, kc, vc, kp = _ring_local_decode(
                        h, bp, cfg, xs[1][f"k_{i}"], xs[1][f"v_{i}"],
                        xs[1][f"pos_{i}"], pos)
                    new_st += [(f"k_{i}", kc), (f"v_{i}", vc),
                               (f"pos_{i}", kp)]
            return h, dict(new_st)

        scan_cache = {k: v for k, v in cache.items() if not k.startswith("t")}
        x, outs = _scan(superblock, x,
                               (params["superblocks"], scan_cache))
        new_cache.update(outs)
        tail = cfg.n_layers - n_super * len(pat)
        for i in range(tail):
            kind = pat[i]
            bp = params[f"tail_{i}"]
            if kind == "rglru":
                hn = rmsnorm(x, bp["attn_norm"], cfg.norm_eps)
                o, (cst, lst) = rec.rglru_block(
                    hn, bp["attn"], cfg,
                    state=(cache[f"tconv_{i}"], cache[f"tlru_{i}"].astype(F32)))
                x = x + o
                x = _mlp(x, bp, cfg)
                new_cache[f"tconv_{i}"] = cst
                new_cache[f"tlru_{i}"] = lst.astype(jnp.dtype(cfg.dtype))
            else:
                x, kc, vc, kp = _ring_local_decode(
                    x, bp, cfg, cache[f"tk_{i}"], cache[f"tv_{i}"],
                    cache[f"tpos_{i}"], pos)
                new_cache[f"tk_{i}"], new_cache[f"tv_{i}"] = kc, vc
                new_cache[f"tpos_{i}"] = kp

    elif cfg.n_experts:   # GQA MoE (arctic)
        def body(h, xs):
            bp, kc, vc = xs
            hn = rmsnorm(h, bp["attn_norm"], cfg.norm_eps)
            o, (kc, vc) = attn.gqa_decode(hn, bp["attn"], cfg, (kc, vc), pos)
            h = h + o
            h, _ = _moe_layer(h, bp, cfg, mesh, variant="psum")
            return h, (kc, vc)
        x, (ks, vs) = _scan(
            body, x, (params["moe_blocks"], cache["k"], cache["v"]))
        new_cache.update({"k": ks, "v": vs})

    else:
        def body(h, xs):
            bp, kc, vc = xs
            h, kc, vc = dense_decode(h, bp, kc, vc)
            return h, (kc, vc)
        x, (ks, vs) = _scan(
            body, x, (params["blocks"], cache["k"], cache["v"]))
        new_cache.update({"k": ks, "v": vs})

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x[:, 0:1], head)[:, 0]
    return logits, new_cache


def make_serve_step(cfg: ArchConfig, mesh: Mesh):
    def serve_step(params, cache, tokens, pos):
        logits, cache = decode_forward(params, cfg, cache, tokens, pos, mesh)
        next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tokens, cache
    return serve_step


def make_prefill_step(cfg: ArchConfig, mesh: Mesh):
    def prefill_step(params, batch):
        logits, _, _, caches = forward(params, cfg, batch, mesh,
                                       collect_cache=True)
        return logits[:, -1], caches
    return prefill_step
