"""Pallas TPU kernel for the BConv accumulation (paper §II-A eq.(1), §IV-D).

out[d, n] = sum_j v[j, n] * w[j, d]  (mod p_d)

This is FHE's all-to-all primitive: in FHEmem the partial products cross
the inter-bank chain network; on TPU each program holds an (8, block_n)
output tile of 8 destination limbs in VMEM and streams the S source limbs
through the VPU with a static unrolled multiply-accumulate (the adder-tree
of §IV-D, depth-1).

Two reduction schedules:
* eager: Montgomery-reduce every partial product (baseline);
* lazy  (`lazy=True`): accumulate 2^31-bounded sums in (hi, lo) u32 pairs
  and fold every 4 products — fewer REDC ops, the §Perf variant. Both are
  exact; tests compare them bit-for-bit against ref.bconv_ref.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import addmod32, mont_mul32, pallas_call
from repro.kernels.modmul import ROWS

U32 = jnp.uint32


def _bconv_kernel(v_ref, w_ref, p_ref, pinv_ref, o_ref):
    """v (S, bn); w_mont (ROWS, S); p/pinv (ROWS, 1). out (ROWS, bn):
    ROWS destination limbs at once, each row with its own modulus."""
    p = p_ref[...]
    pi = pinv_ref[...]
    s = v_ref.shape[0]
    acc = jnp.zeros(o_ref.shape, U32)
    for j in range(s):
        prod = mont_mul32(v_ref[j:j + 1, :], w_ref[:, j:j + 1], p, pi)
        acc = addmod32(acc, prod, p)
    o_ref[...] = acc


def _bconv_kernel_lazy(v_ref, w_ref, p_ref, pinv_ref, o_ref):
    """Lazy variant: defer the modular fold across groups of products.

    mont_mul32 outputs are < p < 2^31; sums of two stay < 2^32. We add
    pairs before the modular fold, halving the addmod count.
    """
    p = p_ref[...]
    pi = pinv_ref[...]
    s = v_ref.shape[0]
    acc = jnp.zeros(o_ref.shape, U32)
    j = 0
    while j < s:
        prod = mont_mul32(v_ref[j:j + 1, :], w_ref[:, j:j + 1], p, pi)
        if j + 1 < s:
            prod2 = mont_mul32(v_ref[j + 1:j + 2, :], w_ref[:, j + 1:j + 2],
                               p, pi)
            pair = prod + prod2                     # < 2^32, no overflow
            pair = jnp.where(pair >= p, pair - p, pair)
            pair = jnp.where(pair >= p, pair - p, pair)
            j += 2
        else:
            pair = prod
            j += 1
        acc = addmod32(acc, pair, p)
    o_ref[...] = acc


def bconv_pallas(v, w_mont, p, pinv_neg, *, block_n: int = 512,
                 lazy: bool = False, interpret: bool = True):
    """v: (S, N) u32 (source values, reduced mod their own q_j);
    w_mont: (D, S) u32 — [qhat_j]_{p_d} in Montgomery form w.r.t. p_d;
    p, pinv_neg: (D,) u32. Returns (D, N) u32."""
    s, n = v.shape
    d = w_mont.shape[0]
    block_n = min(block_n, n)
    # zero-pad the coefficient axis: `n // block_n` grids silently drop
    # the tail block on non-divisible shapes (zero columns are inert);
    # destinations pad to whole (ROWS, block_n) tiles, reusing the last
    # modulus so the discarded rows stay well defined
    pad = (-n) % block_n
    if pad:
        v = jnp.pad(v, ((0, 0), (0, pad)))
    n_pad = n + pad
    pad_d = (-d) % ROWS
    w_mont = jnp.pad(w_mont, ((0, pad_d), (0, 0)), mode="edge")
    p, pinv_neg = [jnp.pad(x, (0, pad_d), mode="edge")[:, None]
                   for x in (p, pinv_neg)]
    grid = ((d + pad_d) // ROWS, n_pad // block_n)
    kern = _bconv_kernel_lazy if lazy else _bconv_kernel
    col = pl.BlockSpec((ROWS, 1), lambda i, j: (i, 0))
    return pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((s, block_n), lambda i, j: (0, j)),
                  pl.BlockSpec((ROWS, s), lambda i, j: (i, 0)),
                  col, col],
        out_specs=pl.BlockSpec((ROWS, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((d + pad_d, n_pad), U32),
        interpret=interpret,
    )(v, w_mont, p, pinv_neg)[:d, :n]
