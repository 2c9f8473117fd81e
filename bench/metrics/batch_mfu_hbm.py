"""Whole batch: compulsory HBM bytes of every op in the schedule that
served each batch, at the chip's HBM bandwidth, over the batches' wall
time. The share of the chip's bandwidth peak that the served work needs;
no compute bound is reckoned (no u32 peak is published)."""
from bench import roofline


def read(run):
    bs = run.window.counted
    if not bs or run.schedule is None:
        return None
    sched = roofline.load("schedule")
    tr, p = run.schedule.trace, run.params
    wall = sum(b.wall_s for b in bs)
    need = sum(sched.batch_bytes(tr.ops, tr.inputs, tr.outputs,
                                 run.start_level, b.n_ct, p.n, p.slots,
                                 p.alpha) for b in bs)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / wall
