import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first lines, before any jax-importing module: jax locks the
#   device count on first init. Only the dry-run sees 512 placeholder
#   devices; smoke tests and benches see the 1 real CPU device.

"""Multi-pod dry-run driver.

For every (architecture x input shape) cell, lower + compile the step on
the production mesh (single-pod 16x16 and multi-pod 2x16x16), record
memory_analysis / cost_analysis / per-collective byte totals parsed from
the compiled HLO, and append to benchmarks/results/dryrun.jsonl.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                 # everything
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --multi-pod     # 512-chip pass
"""
import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.configs import list_archs  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import SHAPES, build_cell  # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results")

COLLECTIVE_RE = re.compile(
    r"=\s*((?:\([^)]*\)|\S+))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8\w*|s64|s32|u64|u32|s16|u16|s8|u8|pred)\[([\d,]*)\]")

DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
               "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
               "s8": 1, "u8": 1, "pred": 1}
for _k in list(DTYPE_BYTES):
    if _k.startswith("f8"):
        DTYPE_BYTES[_k] = 1


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES.get(dt if not dt.startswith("f8") else dt, 1)
    return total


def collective_bytes(hlo_text: str):
    """Sum result bytes of every collective op, by kind (per-device)."""
    out = {}
    for type_str, kind in COLLECTIVE_RE.findall(hlo_text):
        b = _shape_bytes(type_str)
        if kind.endswith("-start"):
            kind = kind[:-6]
        out[kind] = out.get(kind, 0) + b
    return out


def run_cell(arch: str, shape: str, multi_pod: bool) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {"arch": arch, "shape": shape,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "n_devices": 512 if multi_pod else 256}
    cell = build_cell(arch, shape, mesh)
    if cell["skip"]:
        rec.update(status="skipped", reason=cell["reason"])
        return rec
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            jitted = jax.jit(cell["fn"], in_shardings=cell["in_shardings"],
                             out_shardings=cell["out_shardings"])
            lowered = jitted.lower(*cell["args"])
            t_lower = time.time() - t0
            t0 = time.time()
            compiled = lowered.compile()
            t_compile = time.time() - t0
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        rec.update(
            status="ok",
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            flops_per_device=ca.get("flops", 0.0),
            bytes_per_device=ca.get("bytes accessed", 0.0),
            collective_bytes=coll,
            collective_total=sum(coll.values()),
            argument_bytes=getattr(ma, "argument_size_in_bytes", None),
            output_bytes=getattr(ma, "output_size_in_bytes", None),
            temp_bytes=getattr(ma, "temp_size_in_bytes", None),
            hlo_chars=len(hlo),
        )
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   tb=traceback.format_exc()[-2000:])
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    os.makedirs(RESULTS_DIR, exist_ok=True)
    out_path = args.out or os.path.join(RESULTS_DIR, "dryrun.jsonl")
    archs = [args.arch] if args.arch else [
        a.replace("_", "-") for a in list_archs()]
    # canonical dashed names
    from repro.configs import DASHED
    archs = [next(k for k, v in DASHED.items()
                  if v == a.replace("-", "_")) if a.replace("-", "_") in
             DASHED.values() else a for a in archs]
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    n_ok = n_skip = n_err = 0
    with open(out_path, "a") as f:
        for arch in archs:
            for shape in shapes:
                for mp in meshes:
                    rec = run_cell(arch, shape, mp)
                    f.write(json.dumps(rec) + "\n")
                    f.flush()
                    tag = rec["status"]
                    n_ok += tag == "ok"
                    n_skip += tag == "skipped"
                    n_err += tag == "error"
                    msg = (f"[{tag:7s}] {arch:24s} {shape:12s} "
                           f"{rec['mesh']:8s}")
                    if tag == "ok":
                        msg += (f" compile={rec['compile_s']:7.1f}s "
                                f"flops/dev={rec['flops_per_device']:.3e} "
                                f"coll={rec['collective_total']/2**20:.1f}MiB")
                    elif tag == "error":
                        msg += " " + rec["error"][:120]
                    print(msg, flush=True)
    print(f"done: ok={n_ok} skipped={n_skip} errors={n_err} -> {out_path}")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
