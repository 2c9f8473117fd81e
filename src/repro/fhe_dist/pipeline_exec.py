"""Distributed load-save pipeline executor (paper §IV-F on a mesh).

Maps the PipelineSchedule from core/pipeline.py onto the `data` mesh axis:
each data-rank hosts one resident stage per round (its constants stay
on-device for the whole input batch — the "load once per round" property),
and microbatches flow rank-to-rank via collective_permute, GPipe-style.

Stage bodies must be shape-preserving (ciphertexts padded to the round's
max limb count — the standard trick for level-heterogeneous pipelines; the
mapper already levels stages within a round). Heterogeneous stage programs
are dispatched with lax.switch on the rank index, so the whole round is ONE
SPMD program with a rotating ppermute — exactly the paper's Figure 11
timing structure (compute overlapped with neighbor transfer).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _round_body(x_stack, *, stage_fns: Sequence[Callable], axis: str,
                n_micro: int):
    """shard_map body over `axis`. x_stack: (n_micro, ...) microbatches,
    all resident on rank 0 conceptually; we rotate a working buffer.

    Step t: rank r applies its stage to the microbatch that has passed
    ranks 0..r-1; results shift r -> r+1 each step. After
    n_micro + n_ranks - 1 steps, rank n-1 has emitted every microbatch;
    outputs are collected by shifting them around the ring to rank 0's
    output stack (gathered at the end).
    """
    n_dev = len(stage_fns)     # == axis size; static (lax.axis_size is
    rank = jax.lax.axis_index(axis)  # not available on older jax)
    buf = jnp.zeros_like(x_stack[0])
    out_stack = jnp.zeros_like(x_stack)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def apply_stage(x):
        return jax.lax.switch(rank, list(stage_fns), x)

    n_steps = n_micro + n_dev - 1
    for t in range(n_steps):
        # rank 0 injects microbatch t (if any)
        inject = x_stack[jnp.minimum(t, n_micro - 1)]
        buf = jnp.where((rank == 0) & (t < n_micro), inject, buf)
        buf = apply_stage(buf)
        # collect finished microbatch from the last rank
        done_idx = t - (n_dev - 1)
        is_done = (done_idx >= 0) & (done_idx < n_micro)
        out_stack = jnp.where(
            is_done & (rank == n_dev - 1),
            out_stack.at[jnp.maximum(done_idx, 0)].set(buf), out_stack)
        if t != n_steps - 1:
            buf = jax.lax.ppermute(buf, axis, perm)
    # bring outputs to every rank (replicated result)
    return jax.lax.psum(out_stack, axis)


def run_pipeline_round(stage_fns: Sequence[Callable], x_stack, mesh: Mesh,
                       axis: str = "data"):
    """Execute one pipeline round of len(stage_fns) stages over the
    microbatch stack x_stack (n_micro, ...). len(stage_fns) must equal the
    `axis` size. Returns the processed stack (replicated)."""
    n_micro = x_stack.shape[0]
    fn = jax.shard_map(
        partial(_round_body, stage_fns=tuple(stage_fns), axis=axis,
                n_micro=n_micro),
        mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False)
    return fn(x_stack)


def run_load_save_pipeline(rounds: List[Sequence[Callable]], x_stack,
                           mesh: Mesh, axis: str = "data"):
    """Full load-save execution: rounds run sequentially; within a round
    the batch streams through the resident stages (constants loaded once —
    they are closed over by the stage functions, i.e. device-resident)."""
    for fns in rounds:
        x_stack = run_pipeline_round(fns, x_stack, mesh, axis)
    return x_stack
