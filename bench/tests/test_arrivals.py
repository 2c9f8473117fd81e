"""The traffic generator gives every seed the same work: the same arrival
instants, the same sizes in another order, payloads from the seed."""
import numpy as np

from bench import arrivals, harness


def test_open_loop_same_work_any_seed():
    mix = harness.load_cell("ckks_boot_n16.matvec_steady").mix
    a = arrivals.open_loop(mix, 10.0, 2**31 + 99)
    b = arrivals.open_loop(mix, 10.0, 5)
    assert len(a) == len(b) == round(mix["rate_rps"] * 10)
    assert sorted(p.slots for p in a) == sorted(p.slots for p in b)
    assert [p.due_s for p in a] == [p.due_s for p in b]
    assert [p.slots for p in a] != [p.slots for p in b]
    n, rate = len(a), mix["rate_rps"]
    # the gaps: quantiles of the exponential, scaled to fill the window
    q = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    q = q * (10.0 / q.sum())
    for plan in (a, b):
        gaps = np.diff([p.due_s for p in plan])
        idx = np.searchsorted(q, gaps)
        near = np.minimum(idx, n - 1)
        below = np.maximum(idx - 1, 0)
        err = np.minimum(abs(q[near] - gaps), abs(q[below] - gaps))
        assert err.max() < 1e-9
    assert all(0 <= p.due_s <= 10.0 for p in a)
    assert [p.due_s for p in a] == sorted(p.due_s for p in a)
    lo, hi = mix["slots"]
    assert all(lo <= p.slots <= hi and len(p.payload) == p.slots for p in a)
    again = arrivals.open_loop(mix, 10.0, 2**31 + 99)
    assert all(np.array_equal(x.payload, y.payload) for x, y in zip(a, again))


def test_closed_loop_requests_follow_the_client_not_the_clock():
    mix = harness.load_cell("ckks_boot_n16.helr_closed").mix
    one = arrivals.ClosedLoop(mix, 7)
    two = arrivals.ClosedLoop(mix, 7)
    first = one.first()
    assert len(first) == mix["clients"]
    assert {p.tenant for p in first} == {f"tenant{i}"
                                         for i in range(mix["tenants"])}
    two.first()
    x, y = one.next(3, 1.0), two.next(3, 9.0)
    assert np.array_equal(x.payload, y.payload) and x.client == 3
