"""Compulsory bytes of the served kernels and programs, and the chip's
peaks. A residue counts 4 bytes, whatever width the program stores it in,
so a change of representation does not change the count. The bound is
HBM bandwidth: the v5e publishes no u32-multiply peak, so no compute
bound is reckoned."""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
WORD = 4


def peaks(device_kind: str) -> dict:
    """The peaks table's row for ``device_kind``; an unknown device is an
    error, never a default."""
    with open(os.path.join(os.path.dirname(HERE), "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def load(kernel: str):
    """The roofline module of one kernel, ``bench/roofline/<kernel>.py``."""
    spec = importlib.util.spec_from_file_location(
        f"bench_roofline_{kernel}", os.path.join(HERE, f"{kernel}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
