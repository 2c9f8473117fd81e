"""Metric readers: ``bench/metrics/<name>.py`` for each metric of
BENCHMARK.json, found by its name (or, for ``<base>.<suffix>``, by its
base name). Each has ``read(run) -> float | None``; ``run`` carries the
cell, its measured window (``bench.harness.Window``), the set-up seconds,
the parameter set, the served schedule and the chip's peaks. A reader that
finds nothing to read returns None, and the metric is left out."""
from __future__ import annotations

import importlib.util
import os
from typing import List

HERE = os.path.dirname(os.path.abspath(__file__))


def reader(name: str):
    for stem in (name, name.split(".")[0]):
        path = os.path.join(HERE, f"{stem}.py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader bench/metrics/{name}.py")


def latencies_s(run) -> List[float]:
    """Every served request's latency: its batch's wall-clock end minus
    its due time."""
    w = run.window
    return [b.end - w.due[rid] for b in w.counted for rid, _, _ in b.requests]


def per_batch_ms(run, attr: str):
    bs = run.window.counted
    if not bs or not run.window.layers:
        return None
    return 1e3 * sum(getattr(b, attr) for b in bs) / len(bs)
