"""Traffic generator: one general reader of the mixes in bench/traffic/.

Every seed gets the same work: the same arrival instants, and the same
multiset of request sizes in an order drawn from the seed, with payload
values drawn from the seed. So seeds differ in which request comes when
and in its data, not in load or in how bursty the arrivals are.

Open loop (``"loop": "open"``): ``rate_rps`` requests a second over the
window, gaps at the quantiles of an exponential distribution (a Poisson
process with its gaps evened out) in one fixed shuffled order, tenants
round-robin, sizes spread evenly over ``slots = [lo, hi]``.

Closed loop (``"loop": "closed"``): ``clients`` clients, round-robin over
``tenants``, each sending its next request when its previous one is done.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Generator for one stream of a seed; any whole number is a seed."""
    return np.random.default_rng([int(seed) % (1 << 63)] + list(stream))


@dataclasses.dataclass
class Planned:
    """One request of the mix: due time (s from window start), tenant,
    slots and payload values. ``client`` is set in a closed loop."""
    due_s: float
    tenant: str
    slots: int
    payload: np.ndarray
    client: int = -1


def _spread(lo: int, hi: int, n: int) -> np.ndarray:
    return np.rint(lo + (hi - lo) * (np.arange(n) + 0.5) / n).astype(int)


def open_loop(mix: dict, seconds: float, seed: int) -> List[Planned]:
    rate = float(mix["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = gaps * (seconds / gaps.sum())         # exactly the window
    due = np.cumsum(rng_for(0, 2).permutation(gaps)) - gaps.min()
    rng = rng_for(seed, 0)
    lo, hi = mix["slots"]
    sizes = rng.permutation(_spread(lo, hi, n))
    vlo, vhi = mix["values"]
    tenants = int(mix["tenants"])
    return [Planned(float(due[i]), f"tenant{i % tenants}", int(sizes[i]),
                    rng.uniform(vlo, vhi, size=int(sizes[i])))
            for i in range(n)]


class ClosedLoop:
    """Clients of a closed loop; ``next(client)`` is that client's next
    request (sizes and values from the seed, independent of timing)."""

    def __init__(self, mix: dict, seed: int):
        self.mix = mix
        self.seed = seed
        self.clients = int(mix["clients"])
        self.tenants = int(mix["tenants"])
        self._sent = [0] * self.clients

    def next(self, client: int, due_s: float) -> Planned:
        k = self._sent[client]
        self._sent[client] += 1
        rng = rng_for(self.seed, 1, client, k)
        lo, hi = self.mix["slots"]
        slots = int(rng.integers(lo, hi + 1))
        vlo, vhi = self.mix["values"]
        return Planned(due_s, f"tenant{client % self.tenants}", slots,
                       rng.uniform(vlo, vhi, size=slots), client)

    def first(self) -> List[Planned]:
        return [self.next(c, 0.0) for c in range(self.clients)]
