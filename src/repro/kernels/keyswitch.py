"""Fused Pallas kernels for the CKKS key-switch pipeline.

The hot loop of every homomorphic rotation and multiply is generalized
dnum key switching (core/ops.py::key_switch): per digit, ModUp =
iNTT -> BConv -> NTT, then the evk inner product, then ModDown. Run
stage-by-stage that is 7·dnum + 10 host dispatches per keyswitch (see
``keyswitch_staged``) — exactly the dispatch-granularity overhead
HE-PIM/MemFHE identify as the dominant cost of real PIM FHE. This
module collapses the whole pipeline into FOUR ``pl.pallas_call``
launches, independent of digit count, limb count, and batch size:

  A  ``_intt_scale_kernel``   grid (B, L):     fused inverse NTT with
     the n^{-1}·qhat^{-1} scale folded into one Montgomery multiply —
     the ModUp front half for every digit at once (digits partition the
     Q limbs, so "all digit limbs" is just "all limbs").
  B  ``_bconv_ntt_mulacc_kernel``  grid (B, T, digits): per target limb,
     BConv accumulation, forward NTT stages fused with their twiddle
     multiplies, and the evk multiply-accumulate for BOTH key
     components — with the DIGIT LOOP ON-CHIP: the digit grid axis is
     innermost, so the accumulator block stays resident in VMEM across
     digits (revisiting), never round-tripping to HBM.
  C1 ``_intt_scale_kernel``   grid (2B, n_p): ModDown inverse NTT of
     the special limbs of both accumulators (components folded into
     the batch axis).
  C2 ``_moddown_kernel``      grid (2B, L):   BConv P->Q fused with the
     forward NTT, the subtraction, and the P^{-1} multiply.

The digit-limb "copy" of the reference ModUp needs no special case: for
a target limb inside the source digit, every cross term of the BConv
sum vanishes (qhat_j ≡ 0 mod q_i for j ≠ i) and the diagonal term
reproduces a_i exactly, so the uniform BConv+NTT path is bit-identical
to the reference interleave. All arithmetic is the u32 Montgomery layer
of kernels/common.py (word32 RNS, moduli < 2^31), so results are
bit-for-bit equal to the u64 library path — decrypt-equality of the
fused engine route is exact, not approximate. Tested in
tests/test_keyswitch_fused.py; dispatch counts are golden-snapshotted
and compared in benchmarks/fig14_kernels.py.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (addmod32, default_interpret, mont_mul32,
                                  pallas_call, record_dispatch, submod32)
from repro.kernels.limb_ntt import (LANES, LimbNttTables, context_tables,
                                    from_tiles, ntt_fwd, ntt_inv,
                                    tile_shape, to_tiles)

U32 = jnp.uint32
U64 = jnp.uint64
SQ = pl.squeezed


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------
# Every kernel sees whole (R, C) limb tiles (limb_ntt.py layout). Per-limb
# scalars (q, -q^{-1}, scales, BConv weights) ride in SMEM as scalar
# prefetch operands; ``ids`` maps a grid limb to its global prime index,
# which also selects the limb's twiddle tables in the index maps.

def _intt_scale_kernel(ids_ref, q_ref, qi_ref, sc_ref, x_ref, row_ref,
                       lane_ref, o_ref):
    """One limb: inverse NTT fused with a per-limb scale (n^{-1}·qhat^{-1}
    — the iNTT normalization and the BConv input scaling as ONE
    Montgomery multiply)."""
    j = pl.program_id(1)
    g = ids_ref[j]
    q = q_ref[g]
    qi = qi_ref[g]
    x = ntt_inv(x_ref[...], row_ref, lane_ref, q, qi)
    o_ref[...] = mont_mul32(x, sc_ref[j], q, qi)


def _bconv_ntt_mulacc_kernel(ids_ref, q_ref, qi_ref, w_ref, v_ref, row_ref,
                             lane_ref, k0_ref, k1_ref, a0_ref, a1_ref):
    """One (batch, target-limb) output tile, revisited across the digit
    grid axis: BConv over the digit's (padded) source limbs, forward NTT,
    then the evk multiply-accumulate for both key components. Padded
    source limbs carry w = 0 so they contribute nothing."""
    j = pl.program_id(1)
    d = pl.program_id(2)
    n_t = pl.num_programs(1)
    g = ids_ref[j]
    q = q_ref[g]
    qi = qi_ref[g]
    alpha = v_ref.shape[0]
    acc = jnp.zeros(v_ref.shape[1:], U32)
    for jj in range(alpha):                     # adder tree, depth-1
        wgt = w_ref[(d * alpha + jj) * n_t + j]
        acc = addmod32(acc, mont_mul32(v_ref[jj], wgt, q, qi), q)
    raised = ntt_fwd(acc, row_ref, lane_ref, q, qi)
    e0 = mont_mul32(raised, k0_ref[...], q, qi)
    e1 = mont_mul32(raised, k1_ref[...], q, qi)

    @pl.when(d == 0)
    def _():
        a0_ref[...] = e0
        a1_ref[...] = e1

    @pl.when(d != 0)
    def _():
        a0_ref[...] = addmod32(a0_ref[...], e0, q)
        a1_ref[...] = addmod32(a1_ref[...], e1, q)


def _moddown_kernel(ids_ref, q_ref, qi_ref, w_ref, pinv_ref, aq_ref, vp_ref,
                    row_ref, lane_ref, o_ref):
    """ModDown tail for one (batch, Q-limb) tile: BConv P->Q fused with
    the forward NTT, the subtraction from a_Q, and the P^{-1} multiply."""
    j = pl.program_id(1)
    n_q = pl.num_programs(1)
    g = ids_ref[j]
    q = q_ref[g]
    qi = qi_ref[g]
    n_p = vp_ref.shape[0]
    acc = jnp.zeros(vp_ref.shape[1:], U32)
    for jj in range(n_p):
        acc = addmod32(acc, mont_mul32(vp_ref[jj], w_ref[jj * n_q + j],
                                       q, qi), q)
    conv = ntt_fwd(acc, row_ref, lane_ref, q, qi)
    diff = submod32(aq_ref[...], conv, q)
    o_ref[...] = mont_mul32(diff, pinv_ref[j], q, qi)


# ---------------------------------------------------------------------------
# host-precomputed per-level tables
# ---------------------------------------------------------------------------

def _mont_np(arr: np.ndarray, p: int) -> np.ndarray:
    """arr -> arr·R mod p as u32 (arr, R mod p < 2^31: no u64 overflow)."""
    rm = np.uint64((1 << 32) % p)
    return ((arr.astype(np.uint64) * rm) % np.uint64(p)).astype(np.uint32)


@dataclasses.dataclass
class _LevelTables:
    """Per-level scalars of one keyswitch instance (all SMEM-sized)."""
    n_digits: int
    alpha: int                    # padded digit size
    n_p: int
    arrays: Dict[str, jnp.ndarray]
    # q_ids (L,) i32, q_scale_m (L,): n^{-1}·qhat^{-1} mont
    # t_ids (T,) i32, w_m (D·alpha·T,): BConv weights mont w.r.t. target
    # p_ids (n_p,) i32, p_scale_m (n_p,): n^{-1}·phat^{-1} mont
    # wpq_m (n_p·L,): mont w.r.t. q; pinv_m (L,): P^{-1} mod q, mont


class FusedKeySwitch:
    """Executes the fused keyswitch pipeline against one CkksContext.

    Tables are built host-side once per level; evaluation keys are
    Montgomery-converted once per key identity; the whole
    4-kernel pipeline is jitted once per (batch, level) and shared by
    every evk (relin and all Galois keys ride the same compiled fn).
    Tables and keys are arguments of the jitted pipeline, never
    constants baked into it; ``ntt_tables`` defaults to the context's
    own (``limb_ntt.context_tables``).
    """

    DISPATCHES_PER_APPLY = 4      # pallas_call launches per keyswitch

    def __init__(self, ctx, ntt_tables: Optional[LimbNttTables] = None):
        self.ctx = ctx
        self.ntt_tables = (context_tables(ctx) if ntt_tables is None
                           else ntt_tables)
        self._tabs: Dict[int, _LevelTables] = {}
        self._ksk_m: Dict[Tuple, jnp.ndarray] = {}
        self._fns: Dict[Tuple, callable] = {}
        self.n_applied = 0            # keyswitches run through apply()

    # -- tables --------------------------------------------------------------

    def _tables(self, level: int) -> _LevelTables:
        t = self._tabs.get(level)
        if t is not None:
            return t
        ctx = self.ctx
        l = level + 1
        n_p = ctx.n_p
        digits = ctx.params.digit_indices(level)
        d_n = len(digits)
        alpha = ctx.params.alpha
        target = list(range(l)) + ctx.p_idx()
        t_primes = [ctx.primes[i] for i in target]
        t_n = len(target)
        n_inv = np.asarray(ctx.tables.n_inv)

        # stage A: fused n^{-1}·qhat^{-1} per Q limb
        q_scale = np.zeros(l, dtype=np.uint32)
        for dig in digits:
            big_qd = 1
            for j in dig:
                big_qd *= ctx.primes[j]
            for j in dig:
                qj = ctx.primes[j]
                qhat_inv = pow((big_qd // qj) % qj, -1, qj)
                sc = int(n_inv[j]) * qhat_inv % qj
                q_scale[j] = _mont_np(np.array([sc], dtype=np.uint64), qj)[0]

        # stage B: BConv weights per (digit, src, target)
        w = np.zeros((d_n, alpha, t_n), dtype=np.uint32)
        for d, dig in enumerate(digits):
            big_qd = 1
            for j in dig:
                big_qd *= ctx.primes[j]
            for jl, j in enumerate(dig):
                qhat = big_qd // ctx.primes[j]
                for ti, p in enumerate(t_primes):
                    w[d, jl, ti] = _mont_np(
                        np.array([qhat % p], dtype=np.uint64), p)[0]

        # ModDown: n^{-1}·phat^{-1} per P limb, BConv P->Q, P^{-1}
        p_glob = ctx.p_idx()
        big_p = ctx.big_p
        p_scale = np.zeros(n_p, dtype=np.uint32)
        wpq = np.zeros((n_p, l), dtype=np.uint32)
        for i, g in enumerate(p_glob):
            p = ctx.primes[g]
            phat = big_p // p
            sc = int(n_inv[g]) * pow(phat % p, -1, p) % p
            p_scale[i] = _mont_np(np.array([sc], dtype=np.uint64), p)[0]
            for j in range(l):
                qj = ctx.primes[j]
                wpq[i, j] = _mont_np(np.array([phat % qj],
                                              dtype=np.uint64), qj)[0]
        pinv = np.asarray(ctx.p_inv_mod_q)[:l]
        pinv_m = np.array([_mont_np(pinv[j:j + 1], ctx.primes[j])[0]
                           for j in range(l)], dtype=np.uint32)

        arrays = dict(
            q_ids=np.arange(l, dtype=np.int32), q_scale_m=q_scale,
            t_ids=np.array(target, dtype=np.int32), w_m=w.reshape(-1),
            p_ids=np.array(p_glob, dtype=np.int32), p_scale_m=p_scale,
            wpq_m=wpq.reshape(-1), pinv_m=pinv_m)
        t = _LevelTables(n_digits=d_n, alpha=alpha, n_p=n_p,
                         arrays={k: jnp.asarray(v) for k, v in arrays.items()})
        self._tabs[level] = t
        return t

    def ksk_mont(self, key: Tuple, ksk_data: jnp.ndarray) -> jnp.ndarray:
        """An evk over the whole basis in Montgomery form, cached per
        stable key identity: (dnum, 2, n_all, R, C) u32. Every level's
        pipeline reads its digits and target limbs from it in place."""
        m = self._ksk_m.get(key)
        if m is None:
            rm = np.array([(1 << 32) % p for p in self.ctx.primes],
                          dtype=np.uint64)
            m = self._ksk_m[key] = _ksk_to_mont(
                ksk_data, rm, np.array(self.ctx.primes, dtype=np.uint64))
        return m

    # -- pipeline ------------------------------------------------------------

    def _build(self, b: int, level: int, itp: bool):
        """The full 4-kernel pipeline for one (batch, level) signature:
        ``run(d2 (b, l, N) u64, ksk_m, nt, lv) -> (e0, e1)`` where ``nt``
        is the context's LimbNttTables and ``lv`` this level's arrays."""
        t = self._tables(level)
        l = level + 1
        r_n, c_n = tile_shape(self.ctx.n)
        d_n, alpha, n_p = t.n_digits, t.alpha, t.n_p
        t_n = l + n_p
        s_n = self.ctx.log_n
        tile = (r_n, c_n)
        row_tab = (SQ, r_n, LANES)
        lane_tab = (SQ, s_n, 1, c_n)

        # twiddle tiles of the limb that grid axis 1 maps to (ids: the
        # first scalar-prefetch operand)
        tabs = [pl.BlockSpec(row_tab, lambda i, j, ids, *_: (ids[j], 0, 0)),
                pl.BlockSpec(lane_tab,
                             lambda i, j, ids, *_: (ids[j], 0, 0, 0))]

        def grid_spec(n_prefetch, grid, in_specs, out_specs):
            return pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=n_prefetch, grid=grid,
                in_specs=in_specs, out_specs=out_specs)

        def call(kernel, spec, out_shape):
            return pallas_call(kernel, grid_spec=spec, out_shape=out_shape,
                               interpret=itp)

        def run(d2, ksk_m, nt, lv):
            x = to_tiles(d2)                                  # (b, l, R, C)
            limb_out = jax.ShapeDtypeStruct((b, l) + tile, U32)
            # A: ModUp front half for every digit limb at once
            v = call(_intt_scale_kernel, grid_spec(
                4, (b, l),
                [pl.BlockSpec((SQ, SQ) + tile,
                              lambda i, j, *_: (i, j, 0, 0))] + tabs,
                pl.BlockSpec((SQ, SQ) + tile, lambda i, j, *_: (i, j, 0, 0))),
                limb_out)(lv["q_ids"], nt.q32, nt.qinv32, lv["q_scale_m"],
                          x, nt.inv_row, nt.inv_lane)
            # digits partition the Q limbs contiguously in alpha-chunks:
            # zero-pad the tail digit and fold the digit axis out
            v_pad = jnp.pad(v, ((0, 0), (0, d_n * alpha - l), (0, 0), (0, 0))
                            ).reshape((b, d_n, alpha) + tile)
            # B: on-chip digit loop (digit axis innermost -> accumulator
            # tiles stay resident across digits)
            acc_out = jax.ShapeDtypeStruct((b, t_n) + tile, U32)
            acc_spec = pl.BlockSpec((SQ, SQ) + tile,
                                    lambda i, j, d, *_: (i, j, 0, 0))
            acc0, acc1 = call(_bconv_ntt_mulacc_kernel, grid_spec(
                4, (b, t_n, d_n),
                [pl.BlockSpec((SQ, SQ, alpha) + tile,
                              lambda i, j, d, *_: (i, d, 0, 0, 0)),
                 pl.BlockSpec(row_tab,
                              lambda i, j, d, ids, *_: (ids[j], 0, 0)),
                 pl.BlockSpec(lane_tab,
                              lambda i, j, d, ids, *_: (ids[j], 0, 0, 0)),
                 pl.BlockSpec((SQ, SQ, SQ) + tile,
                              lambda i, j, d, ids, *_: (d, 0, ids[j], 0, 0)),
                 pl.BlockSpec((SQ, SQ, SQ) + tile,
                              lambda i, j, d, ids, *_: (d, 1, ids[j], 0, 0))],
                [acc_spec, acc_spec]),
                [acc_out, acc_out])(lv["t_ids"], nt.q32, nt.qinv32,
                                    lv["w_m"], v_pad, nt.fwd_row,
                                    nt.fwd_lane, ksk_m, ksk_m)
            # ModDown: both key components fold into the batch axis
            g = jnp.concatenate([acc0, acc1], axis=0)        # (2b, T, R, C)
            vp = call(_intt_scale_kernel, grid_spec(
                4, (2 * b, n_p),
                [pl.BlockSpec((SQ, SQ) + tile,
                              lambda i, j, *_: (i, l + j, 0, 0))] + tabs,
                pl.BlockSpec((SQ, SQ) + tile, lambda i, j, *_: (i, j, 0, 0))),
                jax.ShapeDtypeStruct((2 * b, n_p) + tile, U32))(
                    lv["p_ids"], nt.q32, nt.qinv32, lv["p_scale_m"],
                    g, nt.inv_row, nt.inv_lane)
            out = call(_moddown_kernel, grid_spec(
                5, (2 * b, l),
                [pl.BlockSpec((SQ, SQ) + tile, lambda i, j, *_: (i, j, 0, 0)),
                 pl.BlockSpec((SQ, n_p) + tile,
                              lambda i, j, *_: (i, 0, 0, 0))] + tabs,
                pl.BlockSpec((SQ, SQ) + tile, lambda i, j, *_: (i, j, 0, 0))),
                jax.ShapeDtypeStruct((2 * b, l) + tile, U32))(
                    lv["q_ids"], nt.q32, nt.qinv32, lv["wpq_m"],
                    lv["pinv_m"], g, vp, nt.fwd_row, nt.fwd_lane)
            shape = (b, l, r_n * c_n)
            return from_tiles(out[:b], shape), from_tiles(out[b:], shape)
        return run

    def apply(self, d2: jnp.ndarray, level: int, ksk_m: jnp.ndarray,
              interpret=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Key-switch d2 (B, level+1, N) u64 NTT-domain to the key in
        ksk_m (from ``ksk_mont``). Returns (e0, e1), each (B, level+1,
        N) u64 — bit-identical to core/ops.key_switch per batch row."""
        itp = default_interpret() if interpret is None else interpret
        b = d2.shape[0]
        key = (b, level, itp)
        fn = self._fns.get(key)
        if fn is None:
            # one fused XLA program holding the 4 kernel launches
            fn = self._fns[key] = jax.jit(self._build(b, level, itp))
        record_dispatch(self.DISPATCHES_PER_APPLY, interpret=itp)
        self.n_applied += 1
        return fn(d2, ksk_m, self.ntt_tables, self._tables(level).arrays)


@jax.jit
def _ksk_to_mont(ksk_data, rm, q):
    """(dnum, 2, n_all, N) u64 evk -> (dnum, 2, n_all, R, C) u32 Montgomery."""
    from repro.core import modarith as ma
    m = ma.mulmod(ksk_data, rm[:, None], q[:, None])
    return to_tiles(m).reshape(ksk_data.shape[:3] +
                               tile_shape(ksk_data.shape[-1]))


# ---------------------------------------------------------------------------
# staged baseline: the same pipeline as one dispatch per stage
# ---------------------------------------------------------------------------

def keyswitch_staged(ctx, d2: jnp.ndarray, level: int, ksk,
                     interpret=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dispatch-per-stage keyswitch through the standalone kernels +
    library NTT dispatches — bit-identical to core/ops.key_switch, used
    as the fig14 baseline the fused pipeline is measured against. Every
    host-side launch records itself via kernels.common.record_dispatch:
    7 per digit (iNTT, qhat^{-1} modmul, BConv, NTT, interleave, 2×evk
    mulacc) plus 10 for ModDown."""
    from repro.kernels import ops as kops
    itp = default_interpret() if interpret is None else interpret
    idx_q = ctx.q_idx(level)
    idx_p = ctx.p_idx()
    target = idx_q + idx_p
    t_primes = [ctx.primes[i] for i in target]
    n = ctx.n
    digits = ctx.params.digit_indices(level)
    acc0 = jnp.zeros((len(target), n), dtype=U64)
    acc1 = jnp.zeros((len(target), n), dtype=U64)
    ksk_sel = ksk.data[:, :, np.array(target)]
    pos = {g: i for i, g in enumerate(target)}
    for d, dig in enumerate(digits):
        other = [i for i in target if i not in dig]
        tabs = ctx.bconv_tables(dig, other)
        record_dispatch()                                   # iNTT
        dig_c = ctx.intt(d2[np.array(dig)], dig)
        src = [ctx.primes[i] for i in dig]
        v = kops.modmul(dig_c, jnp.broadcast_to(tabs.qhat_inv[:, None],
                                                dig_c.shape), src,
                        interpret=itp)
        conv = kops.bconv(v, tabs.w, [ctx.primes[i] for i in other],
                          interpret=itp)
        record_dispatch()                                   # NTT
        conv_ntt = ctx.ntt(conv, other)
        record_dispatch()                                   # interleave
        raised = jnp.zeros((len(target), n), dtype=U64)
        raised = raised.at[np.array([pos[g] for g in dig])].set(
            d2[np.array(dig)])
        raised = raised.at[np.array([pos[g] for g in other])].set(conv_ntt)
        acc0 = kops.mulacc(raised, ksk_sel[d, 0], acc0, t_primes,
                           interpret=itp)
        acc1 = kops.mulacc(raised, ksk_sel[d, 1], acc1, t_primes,
                           interpret=itp)
    nq = len(idx_q)
    q = ctx.q_all[:nq][:, None]
    tabs = ctx.bconv_tables(idx_p, idx_q)
    outs = []
    for acc in (acc0, acc1):
        record_dispatch()                                   # iNTT (P)
        p_c = ctx.intt(acc[nq:], idx_p)
        v = kops.modmul(p_c, jnp.broadcast_to(tabs.qhat_inv[:, None],
                                              p_c.shape),
                        [ctx.primes[i] for i in idx_p], interpret=itp)
        conv = kops.bconv(v, tabs.w, [ctx.primes[i] for i in idx_q],
                          interpret=itp)
        record_dispatch()                                   # NTT
        conv_ntt = ctx.ntt(conv, idx_q)
        record_dispatch()                                   # sub + P^{-1}
        from repro.core import modarith as ma
        diff = ma.submod(acc[:nq], conv_ntt, q)
        outs.append(ma.mulmod(diff, ctx.p_inv_mod_q[:nq][:, None], q))
    return outs[0], outs[1]
