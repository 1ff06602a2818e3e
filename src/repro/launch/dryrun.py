"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the distribution config is coherent (shardings
legal, collectives supported, memory accounted) and extracts the roofline
inputs: HLO FLOPs / bytes from ``compiled.cost_analysis()`` and collective
bytes parsed from the optimized HLO. Results land in
``experiments/dryrun/<arch>__<shape>__<mesh>.json``.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma_2b --shape train_4k
"""
import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax

from repro.configs import ARCH_IDS, ASSIGNED_SHAPES, get_config, supports_shape
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_step

# ---------------------------------------------------------------------------
# HLO collective-traffic analysis
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "c128": 16, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?))\s+"
    r"(all-reduce-start|all-gather-start|reduce-scatter|all-to-all|"
    r"collective-permute-start|all-reduce|all-gather|collective-permute)\(")


def _type_bytes(s: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(s):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_stats(hlo_text: str):
    """Sum result bytes of every collective op in the optimized HLO
    (one SPMD partition = per-device traffic proxy)."""
    per_op = {}
    for m in _COLL_RE.finditer(hlo_text):
        shape_s, op = m.group(1), m.group(2)
        op = op.replace("-start", "")
        b = _type_bytes(shape_s)
        d = per_op.setdefault(op, {"count": 0, "bytes": 0})
        d["count"] += 1
        d["bytes"] += b
    total = sum(d["bytes"] for d in per_op.values())
    return {"per_op": per_op, "total_bytes": total}


# ---------------------------------------------------------------------------
# Hardware model (TPU v5e-like, per chip)
# ---------------------------------------------------------------------------

PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link


def roofline_terms(flops: float, bytes_accessed: float, coll_bytes: float,
                   chips: int):
    """cost_analysis numbers are per-partition (one SPMD module)."""
    return {
        "compute_s": flops / PEAK_FLOPS,
        "memory_s": bytes_accessed / HBM_BW,
        "collective_s": coll_bytes / ICI_BW,
    }


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: Path,
             use_pallas: bool = False, extra_tag: str = "") -> dict:
    from repro.configs import SHAPES_BY_NAME

    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    if not supports_shape(cfg, shape):
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
               "status": "skipped",
               "reason": "long_500k requires sub-quadratic attention "
                         "(see DESIGN.md §Arch-applicability)"}
        _write(out_dir, rec, extra_tag)
        return rec
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.devices.size
    t0 = time.time()
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "chips": chips, "status": "ok"}
    try:
        built = build_step(cfg, mesh, shape)
        lowered = built.lower()
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        # loop-aware accounting (XLA cost_analysis does not scale while
        # bodies by trip count — see hlo_analysis module docstring)
        hc = hlo_analysis.analyze(hlo)
        flops = hc.dot_flops + hc.elementwise_flops
        bytes_acc = hc.traffic_bytes
        terms = roofline_terms(flops, bytes_acc, hc.collective_bytes, chips)
        pc = cfg.param_count()
        tokens = shape.global_batch * (shape.seq_len if shape.kind == "train"
                                       else (shape.seq_len if shape.kind == "prefill" else 1))
        mult = 6 if shape.kind == "train" else 2
        model_flops = mult * pc["active"] * tokens
        rec.update({
            "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
            "memory": {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "generated_code_bytes": ma.generated_code_size_in_bytes,
            },
            "hlo_flops_per_device": flops,
            "hlo_bytes_per_device": bytes_acc,
            "xla_cost_analysis": {  # raw (loop-unscaled) for reference
                "flops": float(ca.get("flops", 0.0)),
                "bytes": float(ca.get("bytes accessed", 0.0)),
            },
            "hlo_detail": hc.as_dict(),
            "roofline": terms,
            "model_flops_total": model_flops,
            "model_flops_per_device": model_flops / chips,
            "useful_flops_ratio": (model_flops / chips) / flops if flops else 0.0,
            "dominant": max(terms, key=terms.get),
            "params_total": pc["total"], "params_active": pc["active"],
        })
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec["status"] = "failed"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    _write(out_dir, rec, extra_tag)
    return rec


def _write(out_dir: Path, rec: dict, extra_tag: str = "") -> None:
    out_dir.mkdir(parents=True, exist_ok=True)
    tag = f"__{extra_tag}" if extra_tag else ""
    path = out_dir / f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{tag}.json"
    path.write_text(json.dumps(rec, indent=1, default=str))


def main() -> None:
    # 512 host devices stand in for the production meshes. Set here, before
    # JAX creates its CPU client, so that importing this module changes
    # nothing.
    jax.config.update("jax_num_cpu_devices", 512)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else args.arch.split(",")
    shapes = ([s.name for s in ASSIGNED_SHAPES] if args.shape == "all"
              else args.shape.split(","))
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    out_dir = Path(args.out)
    t00 = time.time()
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                tag = f"__{args.tag}" if args.tag else ""
                p = out_dir / f"{arch}__{shape}__{mesh_kind}{tag}.json"
                if args.skip_existing and p.exists():
                    print(f"[skip] {p.name}")
                    continue
                rec = run_cell(arch, shape, mesh_kind, out_dir, extra_tag=args.tag)
                dom = rec.get("dominant", "-")
                print(f"[{rec['status']:7s}] {arch:22s} {shape:12s} {mesh_kind:6s} "
                      f"lower={rec.get('lower_s', 0)}s compile={rec.get('compile_s', 0)}s "
                      f"dom={dom} ({time.time() - t00:.0f}s elapsed)",
                      flush=True)
                if rec["status"] == "failed":
                    print(rec["error"], flush=True)


if __name__ == "__main__":
    main()
