"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces a JSON artifact under benchmarks/artifacts/ with:
  memory_analysis   — per-device argument/output/temp bytes (proves it fits)
  cost_analysis     — XLA's flat per-device estimates (single loop iteration)
  hlo_cost          — our trip-count-aware per-device flops / HBM bytes /
                      collective wire bytes (launch.hlo_analysis)
  roofline          — the three terms in seconds + dominant bottleneck
                      (single-pod only, per the assignment)

Usage:
  python -m repro.launch.dryrun                       # all cells, both meshes
  python -m repro.launch.dryrun --arch smollm_135m --shape train_4k
  python -m repro.launch.dryrun --multi-pod           # 2x16x16 only
  python -m repro.launch.dryrun --force               # ignore artifact cache
"""
import argparse
import dataclasses
import json
import os
import time
import traceback
from pathlib import Path

import jax

from repro.configs import ARCH_IDS, SHAPES, get_config, shape_applicable
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_cell
from repro.models.params import count_params

# 512 placeholder host devices for the production meshes.  Set by the entry
# points (``main`` here and in benchmarks/hillclimb.py) before the first
# device query — jax reads it once, when its backend starts.
HOST_DEVICES_FLAG = "--xla_force_host_platform_device_count=512"

ARTIFACT_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "artifacts"

# TPU v5e hardware constants (per chip) — assignment §Roofline.
PEAK_FLOPS_BF16 = 197e12
HBM_BW = 819e9
ICI_BW_PER_LINK = 50e9


def roofline_terms(hlo_cost: hlo_analysis.HloCost, chips: int,
                   cfg, shape) -> dict:
    """Three terms in seconds/step (per-device quantities / per-chip rates)."""
    compute_s = hlo_cost.flops / PEAK_FLOPS_BF16
    memory_s = hlo_cost.hbm_bytes / HBM_BW
    collective_s = hlo_cost.coll_bytes / ICI_BW_PER_LINK
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dom = max(terms, key=terms.get)
    n_active = count_params(cfg, active_only=True)
    tokens = shape.global_batch * (1 if shape.mode == "decode"
                                   else shape.seq_len)
    if shape.mode == "train":
        model_flops = 6.0 * n_active * tokens          # fwd 2ND + bwd 4ND
    else:
        model_flops = 2.0 * n_active * tokens
    model_flops_per_chip = model_flops / chips
    hlo_total = hlo_cost.flops
    return dict(terms, dominant=dom.replace("_s", ""),
                model_flops_per_chip=model_flops_per_chip,
                useful_flop_ratio=(model_flops_per_chip / hlo_total
                                   if hlo_total else 0.0),
                roofline_fraction=(model_flops_per_chip / PEAK_FLOPS_BF16)
                / max(terms.values()) if max(terms.values()) > 0 else 0.0)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             force: bool = False, tag: str = "", cfg_override=None,
             accum=None) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    out_path = ARTIFACT_DIR / f"{arch}__{shape_name}__{mesh_name}{tag}.json"
    if out_path.exists() and not force:
        cached = json.loads(out_path.read_text())
        if cached.get("status") != "error":   # errored cells always retry
            return cached
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
              "mode": shape.mode, "tag": tag}
    if not ok:
        result.update(status="skipped", reason=why)
        _write(out_path, result)
        return result
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        chips = mesh.devices.size
        cell = build_cell(arch, shape_name, mesh, cfg_override=cfg_override,
                          accum=accum)
        with mesh:
            lowered = jax.jit(cell.fn, donate_argnums=cell.donate
                              ).lower(*cell.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = dict(compiled.cost_analysis() or {})
            hlo_text = compiled.as_text()
        hc = hlo_analysis.analyze(hlo_text, chips)
        result.update(
            status="ok",
            chips=chips,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            accum=cell.accum,
            n_params=count_params(cfg),
            n_active_params=count_params(cfg, active_only=True),
            memory_analysis={
                "argument_bytes": int(mem.argument_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes),
                "temp_bytes": int(mem.temp_size_in_bytes),
                "alias_bytes": int(mem.alias_size_in_bytes),
                "peak_estimate_bytes": int(mem.argument_size_in_bytes +
                                           mem.output_size_in_bytes +
                                           mem.temp_size_in_bytes -
                                           mem.alias_size_in_bytes),
            },
            cost_analysis={k: v for k, v in cost.items()
                           if k in ("flops", "bytes accessed",
                                    "transcendentals", "optimal_seconds")},
            hlo_cost={
                "flops_per_device": hc.flops,
                "hbm_bytes_per_device": hc.hbm_bytes,
                "collective_bytes_per_device": hc.coll_bytes,
                "collective_by_kind": hc.coll_by_kind,
                "collective_sites": hc.coll_count,
                "scan_trip_counts": {k: v for k, v in
                                     sorted(hc.trip_counts.items())[:12]},
            },
        )
        if not multi_pod:
            result["roofline"] = roofline_terms(hc, chips, cfg, shape)
    except Exception as e:  # record failures as artifacts too
        result.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:])
    result["wall_s"] = round(time.time() - t0, 2)
    _write(out_path, result)
    return result


def _write(path: Path, obj: dict):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1, default=float))


def main():
    os.environ["XLA_FLAGS"] = HOST_DEVICES_FLAG
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true",
                    help="run only the 2x16x16 multi-pod mesh")
    ap.add_argument("--single-pod", action="store_true",
                    help="run only the 16x16 single-pod mesh")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    archs = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True]
    if args.multi_pod:
        meshes = [True]
    if args.single_pod:
        meshes = [False]
    n_ok = n_skip = n_err = 0
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                r = run_cell(arch, shape, mp, force=args.force)
                tag = {"ok": "OK ", "skipped": "SKIP", "error": "ERR "}[
                    r["status"]]
                extra = ""
                if r["status"] == "ok":
                    mb = r["memory_analysis"]["peak_estimate_bytes"] / 2**30
                    extra = f"peak/dev={mb:7.2f}GiB compile={r['compile_s']:6.1f}s"
                    if "roofline" in r:
                        rf = r["roofline"]
                        extra += (f" dom={rf['dominant']:10s} "
                                  f"frac={rf['roofline_fraction']:.3f}")
                elif r["status"] == "error":
                    extra = r["error"][:120]
                    n_err += 1
                n_ok += r["status"] == "ok"
                n_skip += r["status"] == "skipped"
                print(f"[{tag}] {('2x16x16' if mp else '16x16  ')} "
                      f"{arch:24s} {shape:12s} {extra}", flush=True)
    print(f"done: ok={n_ok} skip={n_skip} err={n_err}")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
