"""Homomorphic op: keyswitches a bootstrap runs (rotations, the
conjugation and relinearizations, each over the whole batch), the mean
of the ``keyswitches`` counter on the program's ``bootstrap`` records
that start inside a counted batch. None where there are none, or where
the ring dropped records inside the window."""
from __future__ import annotations

import bisect


def read(run):
    w = run.window
    bs = sorted(w.counted, key=lambda b: b.start)
    if not bs or not w.layers:
        return None
    try:
        from repro.obs.hook import RING
    except ImportError:
        return None
    recs = RING.records()
    starts = [w.origin + b.start for b in bs]
    if recs and recs[0].idx > 0 and recs[0].end >= starts[0]:
        return None
    counts = []
    for r in recs:
        if r.name != "bootstrap" or "keyswitches" not in r.attrs:
            continue
        i = bisect.bisect_right(starts, r.start) - 1
        if i >= 0 and r.start <= w.origin + bs[i].end:
            counts.append(r.attrs["keyswitches"])
    return sum(counts) / len(counts) if counts else None
