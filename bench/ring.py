"""Per-layer times from the program's own layer records: the always-on
ring of ``repro.obs.hook`` (one record per layer boundary of the
encrypted serving path, on ``time.perf_counter``, the clock the
harness's batch records are on)."""
from __future__ import annotations

import bisect
from typing import Optional


def per_batch_ms(run, name: str) -> Optional[float]:
    """Mean ms per counted batch of the ring records named ``name`` that
    start inside a counted batch's ``[origin + start, origin + end]``.
    None without per-layer times, for a program without the ring, or
    where the ring dropped records inside the window."""
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
    # records end in order; one dropped ended before the oldest kept
    if recs and recs[0].idx > 0 and recs[0].end >= starts[0]:
        return None
    total = 0.0
    for r in recs:
        if r.name != name:
            continue
        i = bisect.bisect_right(starts, r.start) - 1
        if i >= 0 and r.start <= w.origin + bs[i].end:
            total += r.end - r.start
    return 1e3 * total / len(bs)
