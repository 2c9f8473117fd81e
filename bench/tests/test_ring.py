"""The readers of the program's layer ring (``decode_ms``, ``lift_ms``,
``check_ms``, ``gc_ms``) on a synthetic ring: what they count, and when
they read nothing."""
import sys
import types

import pytest

from bench import metrics
from bench.harness import BatchRec
from repro.obs import hook

ORIGIN = 100.0


def _run(batches, layers=True):
    w = types.SimpleNamespace(origin=ORIGIN, layers=layers,
                              counted=[b for b in batches if b.counted])
    return types.SimpleNamespace(window=w)


def _ring(records, size=64):
    ring = hook.Ring(size)
    for name, start, end in records:
        ring.append(name, ORIGIN + start, ORIGIN + end, 1, {})
    return ring


BATCHES = [BatchRec(start=0.0, end=1.0), BatchRec(start=2.0, end=3.0),
           BatchRec(start=4.0, end=5.0, counted=False)]
RECORDS = [("decode", 0.5, 0.9), ("lift", 0.6, 0.7), ("lift", 0.7, 0.8),
           ("check", 0.9, 0.95),
           ("decode", 2.4, 2.6), ("lift", 2.45, 2.5), ("gc", 2.46, 2.47),
           ("decode", 1.2, 1.5),        # between batches
           ("decode", 4.1, 4.9),        # in a batch not counted
           ("decode", -0.5, 0.2)]       # starts before the window


@pytest.mark.parametrize("name,want", [("decode_ms", (400 + 200) / 2),
                                       ("lift_ms", (100 + 100 + 50) / 2),
                                       ("check_ms", 50 / 2),
                                       ("gc_ms", 10 / 2)])
def test_mean_per_counted_batch(monkeypatch, name, want):
    monkeypatch.setattr(hook, "RING", _ring(RECORDS))
    got = metrics.reader(f"{name}.steady")(_run(BATCHES))
    assert got == pytest.approx(want)


def test_no_record_reads_zero(monkeypatch):
    monkeypatch.setattr(hook, "RING", _ring([("decode", 0.5, 0.9)]))
    assert metrics.reader("gc_ms.closed")(_run(BATCHES)) == 0.0


def test_nothing_to_read(monkeypatch):
    monkeypatch.setattr(hook, "RING", _ring(RECORDS))
    read = metrics.reader("decode_ms.steady")
    assert read(_run(BATCHES, layers=False)) is None
    assert read(_run([BatchRec(start=0.0, end=1.0, counted=False)])) is None


def test_drops_inside_the_window_read_nothing(monkeypatch):
    read = metrics.reader("decode_ms.steady")
    # dropped records all ended before the window: still read
    early = [("decode", -3.0 + 0.1 * i, -2.95 + 0.1 * i) for i in range(8)]
    monkeypatch.setattr(hook, "RING", _ring(early + RECORDS, size=12))
    assert hook.RING.dropped == 6
    assert read(_run(BATCHES)) == pytest.approx(300.0)
    # one dropped record may have ended inside the window
    monkeypatch.setattr(hook, "RING", _ring(RECORDS, size=8))
    assert hook.RING.dropped == 2
    assert read(_run(BATCHES)) is None


def test_program_without_the_ring(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.obs.hook", None)
    assert metrics.reader("lift_ms.closed")(_run(BATCHES)) is None
