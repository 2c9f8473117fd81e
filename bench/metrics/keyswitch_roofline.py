"""Fused keyswitch kernels: compulsory HBM bytes of every call in the
traced window at the chip's HBM bandwidth, over the device time of the
kernels' ops. Bandwidth-bound: no u32 compute peak is published."""
from bench import roofline


def read(run):
    t = run.window.trace
    ks = roofline.load("keyswitch")
    if t is None or not run.window.ks_calls:
        return None
    dev_s = sum(t.kernel_time_s.get(k, 0.0) for k in ks.OPS)
    if dev_s <= 0:
        return None
    p = run.params
    need = sum(ks.bytes_per_call(b, lvl, p.n, p.alpha)
               for b, lvl in run.window.ks_calls)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / dev_s
