"""Fig. 14 analogue: processing-technology comparison. The paper compares
FHEmem's near-mat PIM against SIMDRAM/DRISA; here we compare the compute
paths available to this framework on the same NTT/modmul work:

  * pure-jnp reference (ref.py oracle)                 <- "conventional"
  * jit'd iterative NTT (library path)                 <- production CPU
  * Pallas four-step kernel, interpret mode            <- TPU-target logic
  * modmul reduction strategies (generic/Barrett/Montgomery/Solinas)
  * fused keyswitch pipeline vs dispatch-per-stage     <- launch-count win

The keyswitch section is the headline: the fused pipeline
(repro/kernels/keyswitch.py) covers a full generalized keyswitch in 4
kernel launches where the stage-by-stage route needs 7*digits + 10, and
both are bit-equal to the library path — so the dispatch reduction is
asserted (>= 4x), not just reported.

Interpret-mode timings are NOT TPU performance (the kernel body runs as
Python/jnp per block); the comparison is about op-count structure — the
derived column reports per-coefficient work.

    PYTHONPATH=src python -m benchmarks.fig14_kernels [--smoke]

Emits ``name,us_per_call,derived`` CSV rows (benchmarks/run.py contract)
and rewrites ``benchmarks/results/fig14_kernels.jsonl`` for report.py.
"""
import argparse
import json
import os
import sys

import numpy as np
import jax.numpy as jnp

from benchmarks.common import row, timeit
from repro.core import modarith as ma
from repro.core import ntt as nttm
from repro.core.context import CkksContext
from repro.core.encryptor import CkksEncryptor
from repro.core.params import (find_2nth_root, find_ntt_primes,
                               test_params)
from repro.kernels import common as kcom
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.keyswitch import FusedKeySwitch, keyswitch_staged

RESULTS = os.path.join(os.path.dirname(__file__), "results")


def _emit(records, name, us, derived="", **extra):
    row(name, us, derived)
    records.append({"name": name, "us_per_call": us, "derived": derived,
                    **extra})


def keyswitch_comparison(records, smoke: bool) -> None:
    """Fused 4-launch keyswitch vs the dispatch-per-stage route: count
    kernel dispatches on both (asserting the >= 4x reduction the fused
    pipeline exists for) and time them in interpret mode."""
    if smoke:
        params = test_params(log_n=8, n_levels=4, dnum=2, log_scale=26)
    else:
        params = test_params(log_n=10, n_levels=8, dnum=2, log_scale=26)
    level = params.n_levels
    ctx = CkksContext(params)
    enc = CkksEncryptor(ctx, seed=11)
    rk = enc.relin_keygen(enc.keygen())
    rng = np.random.default_rng(0)
    d2 = jnp.asarray(np.stack([
        rng.integers(0, int(q), size=ctx.n, dtype=np.uint64)
        for q in ctx.primes[:level + 1]])[None])

    fks = FusedKeySwitch(ctx)
    km = fks.ksk_mont("relin", rk.data)
    kcom.reset_dispatch_count()
    fks.apply(d2, level, km, interpret=True)
    fused_disp = kcom.dispatch_count()
    kcom.reset_dispatch_count()
    keyswitch_staged(ctx, d2[0], level, rk, interpret=True)
    staged_disp = kcom.dispatch_count()
    digits = len(params.digit_indices(level))
    reduction = staged_disp / fused_disp
    assert fused_disp == FusedKeySwitch.DISPATCHES_PER_APPLY, fused_disp
    assert reduction >= 4.0, (
        f"fused keyswitch must cut dispatches >= 4x: "
        f"staged={staged_disp} fused={fused_disp}")

    iters = 2 if smoke else 3
    t_fused = timeit(lambda: fks.apply(d2, level, km, interpret=True),
                     warmup=1, iters=iters)
    t_staged = timeit(
        lambda: keyswitch_staged(ctx, d2[0], level, rk, interpret=True),
        warmup=1, iters=iters)
    _emit(records, "fig14_keyswitch_fused_pallas", t_fused * 1e6,
          f"4 launches, digits={digits} level={level}; interpret mode",
          dispatches=fused_disp, digits=digits, level=level,
          log_n=params.log_n)
    _emit(records, "fig14_keyswitch_staged_pallas", t_staged * 1e6,
          f"{staged_disp} launches (7*digits+10); interpret mode",
          dispatches=staged_disp, digits=digits, level=level,
          log_n=params.log_n)
    _emit(records, "fig14_keyswitch_dispatch_reduction", 0.0,
          f"{staged_disp}/{fused_disp} = {reduction:.2f}x (asserted >= 4x)",
          staged_dispatches=staged_disp, fused_dispatches=fused_disp,
          reduction=reduction)


def main(argv=()) -> None:
    # argv defaults to () so benchmarks/run.py can call main() without
    # this parser swallowing run.py's own flags
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small ring + short timing loops, fast CI check")
    args = ap.parse_args(list(argv))

    log_n = 8 if args.smoke else 12
    n = 1 << log_n
    mod = find_ntt_primes(30, log_n, 1)[0]
    q = mod.value
    psi = find_2nth_root(q, 2 * n)
    rng = np.random.default_rng(0)
    a = rng.integers(0, q, size=n, dtype=np.uint64)
    tabs = nttm.NttTables([mod], log_n)
    aj = jnp.asarray(a[None])
    records = []

    t = timeit(lambda: nttm.ntt(aj, tabs))
    _emit(records, "fig14_ntt_iterative_jit", t * 1e6, f"N=2^{log_n}")
    kern = kops.NttKernel(q, psi, log_n, log_n // 2)
    a1 = jnp.asarray(a)
    t = timeit(lambda: kern(a1, interpret=True), warmup=1, iters=3)
    _emit(records, "fig14_ntt_fourstep_pallas_interpret", t * 1e6,
          "TPU-target kernel; interpret mode")
    ft = kref.FourStepTables(q, psi, log_n, log_n // 2)
    t = timeit(lambda: kref.four_step_ntt_ref(a1, ft), warmup=1, iters=3)
    _emit(records, "fig14_ntt_fourstep_ref", t * 1e6)

    # modmul reduction strategies (paper §IV-B: Montgomery-friendly moduli)
    b = rng.integers(0, q, size=(4, n), dtype=np.uint64)
    bj = jnp.asarray(b)
    qv = jnp.uint64(q)
    _emit(records, "fig14_modmul_generic", 1e6 * timeit(
        lambda: ma.mulmod(bj, bj, qv)), "u64 remainder")
    mu = jnp.uint64(ma.barrett_mu(q))
    _emit(records, "fig14_modmul_barrett", 1e6 * timeit(
        lambda: ma.mulmod_barrett(bj, bj, qv, mu)))
    qi = jnp.uint64(ma.mont_qinv_neg(q))
    _emit(records, "fig14_modmul_montgomery", 1e6 * timeit(
        lambda: ma.mont_mul(bj, bj, qv, qi)))
    bb, ss = mod.solinas
    _emit(records, "fig14_modmul_solinas_shiftadd", 1e6 * timeit(
        lambda: ma.mulmod_solinas(bj, bj, qv, bb, ss)),
        f"q=2^{bb}-2^{ss}+1 hamming={mod.hamming_weight}")

    # bconv kernel schedules
    src = [m.value for m in find_ntt_primes(28, 10, 6)]
    dst = [m.value for m in find_ntt_primes(30, 10, 4)]
    bn = 256 if args.smoke else 1024
    v = np.stack([rng.integers(0, p, size=bn, dtype=np.uint64)
                  for p in src])
    w = rng.integers(0, min(dst), size=(6, 4), dtype=np.uint64)
    vj, wj = jnp.asarray(v), jnp.asarray(w)
    _emit(records, "fig14_bconv_kernel_eager", 1e6 * timeit(
        lambda: kops.bconv(vj, wj, dst, lazy=False, interpret=True),
        warmup=1, iters=3))
    _emit(records, "fig14_bconv_kernel_lazy", 1e6 * timeit(
        lambda: kops.bconv(vj, wj, dst, lazy=True, interpret=True),
        warmup=1, iters=3), "deferred modular folds")

    keyswitch_comparison(records, args.smoke)

    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "fig14_kernels.jsonl"), "w") as f:
        for r in records:
            f.write(json.dumps({**r, "smoke": bool(args.smoke)}) + "\n")


if __name__ == "__main__":
    main(sys.argv[1:])
