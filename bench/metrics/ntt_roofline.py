"""Limb-NTT kernel: compulsory HBM bytes of each launch in the traced
window (limb rows from the launch's shape in the trace) at the chip's HBM
bandwidth, over the launches' device time."""
from bench import roofline


def read(run):
    t = run.window.trace
    if t is None:
        return None
    nt = roofline.load("limb_ntt")
    ops = [o for o in t.kernel_ops(nt.OPS) if o.shape]
    dev_s = sum(o.dur_ns for o in ops) * 1e-9
    if dev_s <= 0:
        return None
    n = run.params.n
    need = sum(nt.bytes_per_call(nt.limbs_of_shape(o.shape, n), n)
               for o in ops)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / dev_s
