import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ first lines — see dryrun.py. Roofline runs on the single-pod 16x16 mesh.

"""Roofline driver (EXPERIMENTS.md §Roofline).

XLA's cost_analysis does not multiply while-loop bodies by trip count, so
full-depth scanned models under-report FLOPs/bytes/collectives. We instead
lower UNROLLED reduced-depth configs at two depth knobs (k=1, 2), take the
per-layer slope, and extrapolate linearly to the full depth — exact for
homogeneous stacks, and the recurrence (time-axis) scans that cannot be
unrolled get small documented analytic corrections.

Terms per (arch x shape) on the 16x16 production mesh (v5e numbers):
    compute_s    = flops_per_device / 197e12
    memory_s     = bytes_per_device / 819e9
    collective_s = collective_bytes_per_device / 50e9     (per-link ICI)
    MODEL_FLOPS  = 6*N_active*tokens (train) / 2*N_active*tokens (inference)
    useful ratio = MODEL_FLOPS / (flops_per_device * n_devices)
    roofline fraction = useful-compute-time / max(term)
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import numpy as np  # noqa: E402
import jax  # noqa: E402

from repro.configs import get_config, DASHED  # noqa: E402
from repro.launch.dryrun import RESULTS_DIR, collective_bytes  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import SHAPES, build_cell, cell_applicable  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models import layers as L  # noqa: E402

PEAK_FLOPS = 197e12        # bf16 / chip
HBM_BW = 819e9             # bytes/s / chip
ICI_BW = 50e9              # bytes/s / link

CANONICAL = [k for k in DASHED if "_" not in k]


def scaled_cfgs(arch: str, knob: int):
    """Return [(tag, cfg, knob_units)] lowered at this depth knob."""
    cfg = get_config(arch)
    out = []
    if cfg.enc_dec:
        out.append(("encdec", dataclasses.replace(
            cfg, n_layers=knob, n_enc_layers=knob), knob))
    elif cfg.xattn_period:
        per = cfg.xattn_period + 1
        out.append(("superblock", dataclasses.replace(
            cfg, n_layers=per * knob), knob))
    elif cfg.rglru:
        per = len(cfg.block_pattern or ("rglru", "rglru", "attn"))
        out.append(("superblock", dataclasses.replace(
            cfg, n_layers=per * knob), knob))
    elif cfg.n_experts and cfg.first_k_dense:
        out.append(("moe", dataclasses.replace(
            cfg, n_layers=knob, first_k_dense=0), knob))
        out.append(("dense", dataclasses.replace(
            cfg, n_layers=knob, first_k_dense=0, n_experts=0,
            n_shared_experts=0, mtp=False), knob))
    else:
        out.append(("layer", dataclasses.replace(cfg, n_layers=knob), knob))
    return out


def full_knobs(arch: str):
    """(units per tag) at full depth, matching scaled_cfgs tags."""
    cfg = get_config(arch)
    if cfg.enc_dec:
        return {"encdec": cfg.n_layers}
    if cfg.xattn_period:
        return {"superblock": cfg.n_layers // (cfg.xattn_period + 1)}
    if cfg.rglru:
        per = len(cfg.block_pattern or ("rglru", "rglru", "attn"))
        return {"superblock": cfg.n_layers / per}   # 26/3: tail ~ 2/3 sb
    if cfg.n_experts and cfg.first_k_dense:
        return {"moe": cfg.n_layers - cfg.first_k_dense,
                "dense": cfg.first_k_dense}
    return {"layer": cfg.n_layers}


def _measure(cfg, shape: str, mesh) -> dict:
    """Lower+compile one unrolled config; return flops/bytes/collectives."""
    cell = build_cell(cfg.name, shape, mesh, cfg_override=cfg)
    assert not cell["skip"], cell.get("reason")
    with jax.set_mesh(mesh):
        jitted = jax.jit(cell["fn"], in_shardings=cell["in_shardings"],
                         out_shardings=cell["out_shardings"])
        lowered = jitted.lower(*cell["args"])
        compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": coll, "coll_total": float(sum(coll.values()))}


def recurrence_correction(arch: str, shape: str) -> float:
    """Analytic per-device FLOPs for time-axis scans (not unrollable).

    RWKV6 state update: ~4 ops x H x dh x dh per token per layer;
    RG-LRU: ~8 ops x width per token per layer (2/3 of layers).
    Train counts fwd + bwd + remat-refwd (x4); inference x1.
    """
    cfg = get_config(arch)
    sh = SHAPES[shape]
    tokens = sh["batch"] * (1 if sh["kind"] == "decode" else sh["seq"])
    factor = 4.0 if sh["kind"] == "train" else 1.0
    if cfg.rwkv:
        h = cfg.d_model // 64
        per_tok_layer = 4 * h * 64 * 64
        total = per_tok_layer * cfg.n_layers * tokens * factor
    elif cfg.rglru:
        w = cfg.lru_width or cfg.d_model
        per_tok_layer = 8 * w
        total = per_tok_layer * (cfg.n_layers * 2 / 3) * tokens * factor
    else:
        return 0.0
    return total / 256   # per device on the 16x16 mesh


def model_flops(arch: str, shape: str) -> float:
    cfg = get_config(arch)
    sh = SHAPES[shape]
    n_active = cfg.active_param_count()
    tokens = sh["batch"] * (1 if sh["kind"] == "decode" else sh["seq"])
    mult = 6.0 if sh["kind"] == "train" else 2.0
    return mult * n_active * tokens


def run_cell(arch: str, shape: str, mesh, k1=1, k2=2) -> dict:
    rec = {"arch": arch, "shape": shape, "mesh": "16x16", "n_devices": 256}
    cfg = get_config(arch)
    ok, why = cell_applicable(cfg, shape)
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec
    try:
        M.SCAN_UNROLL = True
        L.FLASH_UNROLL = True
        L.FLASH_CHUNK = 4096
        totals = {"flops": 0.0, "bytes": 0.0, "coll_total": 0.0}
        coll_kinds = {}
        fk = full_knobs(arch)
        t0 = time.time()
        for (tag, c1, u1), (_, c2, u2) in zip(scaled_cfgs(arch, k1),
                                              scaled_cfgs(arch, k2)):
            m1 = _measure(c1, shape, mesh)
            m2 = _measure(c2, shape, mesh)
            units = fk[tag]
            for key in ("flops", "bytes", "coll_total"):
                slope = (m2[key] - m1[key]) / (u2 - u1)
                base = m1[key] - slope * u1
                contrib = base + slope * units
                if tag == "dense":       # dense pair: slope only (outer
                    contrib = slope * units   # terms already in the moe pair)
                else:
                    # depth-monotone floor: full depth >= depth-2 measurement
                    # (guards small-cell extrapolation noise)
                    contrib = max(contrib, m2[key])
                totals[key] += contrib
            kinds = set(m1["coll"]) | set(m2["coll"])
            for kk in kinds:
                a, b = m1["coll"].get(kk, 0), m2["coll"].get(kk, 0)
                slope = (b - a) / (u2 - u1)
                base = a - slope * u1
                contrib = (slope * units if tag == "dense"
                           else max(base + slope * units, b))
                coll_kinds[kk] = coll_kinds.get(kk, 0.0) + contrib
        totals["flops"] += recurrence_correction(arch, shape)
        mf = model_flops(arch, shape)
        compute_s = totals["flops"] / PEAK_FLOPS
        memory_s = totals["bytes"] / HBM_BW
        coll_s = totals["coll_total"] / ICI_BW
        terms = {"compute_s": compute_s, "memory_s": memory_s,
                 "collective_s": coll_s}
        dom = max(terms, key=terms.get)
        useful_ratio = mf / max(totals["flops"] * 256, 1.0)
        useful_time = mf / (256 * PEAK_FLOPS)
        rec.update(
            status="ok", measure_s=round(time.time() - t0, 1),
            flops_per_device=totals["flops"],
            bytes_per_device=totals["bytes"],
            collective_bytes_per_device=totals["coll_total"],
            collective_by_kind={k: float(v) for k, v in coll_kinds.items()},
            **{k: float(v) for k, v in terms.items()},
            dominant=dom,
            model_flops=mf,
            useful_flops_ratio=float(useful_ratio),
            roofline_fraction=float(useful_time / max(terms.values())),
        )
    except Exception as e:  # noqa: BLE001
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   tb=traceback.format_exc()[-1500:])
    finally:
        M.SCAN_UNROLL = False
        L.FLASH_UNROLL = False
        L.FLASH_CHUNK = 0
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    os.makedirs(RESULTS_DIR, exist_ok=True)
    out_path = args.out or os.path.join(RESULTS_DIR, "roofline.jsonl")
    mesh = make_production_mesh(multi_pod=False)
    archs = [args.arch] if args.arch else CANONICAL
    shapes = [args.shape] if args.shape else list(SHAPES)
    with open(out_path, "a") as f:
        for arch in archs:
            for shape in shapes:
                rec = run_cell(arch, shape, mesh)
                f.write(json.dumps(rec) + "\n")
                f.flush()
                if rec["status"] == "ok":
                    print(f"[ok] {arch:24s} {shape:12s} "
                          f"comp={rec['compute_s']*1e3:9.3f}ms "
                          f"mem={rec['memory_s']*1e3:9.3f}ms "
                          f"coll={rec['collective_s']*1e3:9.3f}ms "
                          f"dom={rec['dominant'][:-2]:10s} "
                          f"rf={rec['roofline_fraction']:.3f}", flush=True)
                else:
                    print(f"[{rec['status']}] {arch} {shape} "
                          f"{rec.get('error', rec.get('reason', ''))[:120]}",
                          flush=True)


if __name__ == "__main__":
    main()
