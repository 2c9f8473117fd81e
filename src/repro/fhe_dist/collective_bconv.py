"""Distributed BConv: the paper's inter-bank all-to-all (§III-C, §IV-D)
as mesh collectives, in two schedules.

* `bconv_allgather` — the "channel IO" baseline (paper Base1): every
  device gathers all source limbs (one all-gather over `model`), then
  reduces its own output limbs locally. One bulk collective on the
  shared-bus analogue.
* `bconv_ring` — the "partial chain network" (the paper's contribution):
  source limbs circulate around the `model` ring via collective-permute;
  each hop's chunk is multiply-accumulated into the local output limbs
  while the next chunk is in flight. Same total bytes, but neighbor links
  only + compute/communication overlap — exactly the paper's argument for
  the chain over the bus.

Both are shard_map bodies over the `model` axis; tests (multi-device
subprocess) check bit-exactness against rns.bconv.
"""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import modarith as ma


def _local_reduce(v_chunk, w_chunk, dst_q):
    """Accumulate w^T v for one source chunk: v (s, N), w (s, D_l) ->
    (D_l, N) reduced mod dst_q (D_l, 1)."""
    s = v_chunk.shape[0]
    acc = None
    for j in range(s):
        term = ma.mulmod(v_chunk[j][None, :], w_chunk[j][:, None], dst_q)
        acc = term if acc is None else ma.addmod(acc, term, dst_q)
    return acc


def bconv_allgather_body(v_local, qhat_inv_local, src_q_local, w_local,
                         dst_q_local, *, axis: str):
    """shard_map body. v_local (S_l, N): this device's source limbs.
    w_local (S, D_l): full source column of the weight matrix for the
    device's D_l output limbs. Returns (D_l, N)."""
    vs = ma.mulmod(v_local, qhat_inv_local[:, None], src_q_local[:, None])
    v_all = jax.lax.all_gather(vs, axis, tiled=True)          # (S, N)
    return _local_reduce(v_all, w_local, dst_q_local[:, None])


def bconv_ring_body(v_local, qhat_inv_local, src_q_local, w_local,
                    dst_q_local, *, axis: str):
    """Ring schedule: rotate the local chunk around the `model` ring,
    accumulating into the local outputs at each hop (chain network)."""
    n_dev = jax.lax.axis_size(axis)
    my = jax.lax.axis_index(axis)
    vs = ma.mulmod(v_local, qhat_inv_local[:, None], src_q_local[:, None])
    s_l = vs.shape[0]
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    acc = jnp.zeros((w_local.shape[1], vs.shape[1]), jnp.uint64)
    chunk = vs
    for hop in range(n_dev):
        # chunk currently holds the limbs of device (my - hop) mod n_dev
        src_dev = (my - hop) % n_dev
        # select the matching weight rows (static per-hop dynamic slice)
        w_rows = jax.lax.dynamic_slice_in_dim(w_local, src_dev * s_l, s_l, 0)
        part = _local_reduce(chunk, w_rows, dst_q_local[:, None])
        acc = ma.addmod(acc, part, dst_q_local[:, None])
        if hop != n_dev - 1:
            chunk = jax.lax.ppermute(chunk, axis, perm)
    return acc


@partial(jax.jit, static_argnames=("mesh", "variant"))
def distributed_bconv(v, qhat_inv, src_q, w, dst_q, mesh: Mesh,
                      variant: str = "ring"):
    """v: (S, N) coeff-domain source (already reduced mod src primes);
    w: (S, D); returns (D, N). S and D must divide the `model` axis size.
    """
    body = bconv_ring_body if variant == "ring" else bconv_allgather_body
    axis = "model"
    fn = jax.shard_map(
        partial(body, axis=axis), mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(axis), P(None, axis), P(axis)),
        out_specs=P(axis, None), check_vma=False)
    return fn(v, qhat_inv, src_q, w, dst_q)


def bconv_tables_device(ctx, src_idx, dst_idx):
    """(qhat_inv, src_q, w, dst_q) arrays for distributed_bconv."""
    t = ctx.bconv_tables(src_idx, dst_idx)
    return t.qhat_inv, t.src_q, t.w, t.dst_q
