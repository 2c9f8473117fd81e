"""The control of the correctness check fails it: the plain reference,
computed in the precision the configuration names as the next below its
own (the mix's ``control_precision``), put in the program's place on a window's
worth of the cell's traffic at the cell's own sizes, reads a gap above the
limit on every seed tried. The program is not needed for this: the
control replaces its outputs."""
import json
import os

import pytest

from bench import arrivals, control, harness

with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]
SEEDS = [3, 2**31 + 17, 901]


def _window(cell, seed, slots):
    """Batches as the slot batcher packs the mix's requests, without
    serving them (their outputs are the control's to fill)."""
    from repro.runtime.batcher import pack_slot_groups
    from repro.runtime.queue import Request
    mix = cell.mix
    seconds = float(cell.bench["run_seconds"])
    if mix["loop"] == "open":
        planned = arrivals.open_loop(mix, seconds, seed)
    else:
        planned = arrivals.ClosedLoop(mix, seed).first()
    reqs = [Request(i, p.tenant, cell.program, p.due_s, p.slots,
                    payload=p.payload) for i, p in enumerate(planned)]
    cap = int(mix["max_batch"])
    batches = []
    while reqs:
        groups, reqs = pack_slot_groups(reqs, slots, cap)
        rec = harness.BatchRec(start=0.0, n_ct=cap)
        rec.slot_groups = [[(r.request_id, r.slots_needed) for r in g]
                           for g in groups]
        rec.requests = [(r.request_id, r.arrival_s, r.slots_needed)
                        for g in groups for r in g]
        batches.append(rec)
    payloads = {i: p.payload for i, p in enumerate(planned)}
    return harness.Window(seed, seconds, batches, payloads, {},
                          len(planned), [], 0.0, 0, 0.0, [])


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_the_limit(name, seed):
    cell = harness.load_cell(name)
    slots = 1 << int(cell.config["params"]["log_n"]) - 1
    w = _window(cell, seed, slots)
    got = control.control_reading(cell, w, slots,
                                  cell.mix["control_precision"])
    assert got > cell.limit
