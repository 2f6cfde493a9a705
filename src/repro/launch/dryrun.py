import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (arch × shape × mesh) lowers+compiles.

The FIRST two lines above must run before any jax import (jax locks the
device count at first init); 512 placeholder host devices back both the
single-pod 16×16 mesh and the 2×16×16 multi-pod mesh.

For each combination this script:
  1. builds the step bundle (ShapeDtypeStruct inputs — zero allocation);
  2. ``.lower()`` + ``.compile()`` under the production mesh;
  3. records ``memory_analysis()`` / ``cost_analysis()`` / collective
     bytes parsed from the optimized HLO into
     ``artifacts/dryrun/<arch>__<shape>__<mesh>.json`` —
     the roofline table (§Roofline, benchmarks/roofline.py) reads these.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-0.5b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only]
"""

import argparse
import json
import time
import traceback
from typing import Optional

import jax

from repro.configs.base import ARCH_IDS, SHAPES, get_config
from repro.launch.hlo_analysis import analyze_collectives
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_bundle

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                         "artifacts", "dryrun")

# (arch, shape) pairs skipped, each with its reason
SKIPS = {
    ("whisper-large-v3", "long_500k"):
        "enc-dec with a 448-token decoder spec; 500k decode is architecture-"
        "inapplicable",
    ("qwen1.5-110b", "long_500k"):
        "pure full attention, no windowed variant in the source model",
    ("internvl2-76b", "long_500k"):
        "pure full attention, no windowed variant in the source model",
    ("grok-1-314b", "long_500k"):
        "pure full attention, no windowed variant in the source model",
}


def run_one(arch: str, shape_name: str, multi_pod: bool,
            save: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "status": "pending"}

    if (arch, shape_name) in SKIPS:
        rec.update(status="skipped", reason=SKIPS[(arch, shape_name)])
        _save(rec, save)
        return rec

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_dev = mesh.devices.size
        bundle = build_bundle(cfg, shape, mesh)
        lowered = bundle.lower()
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        mem_rec = {}
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            try:
                mem_rec[attr] = int(getattr(mem, attr))
            except Exception:
                pass
        cost = compiled.cost_analysis() or {}
        cost_rec = {k: float(v) for k, v in cost.items()
                    if isinstance(v, (int, float)) and (
                        "flops" in k or "bytes" in k or "utilization" in k.lower())}
        hlo = compiled.as_text()
        colls = analyze_collectives(hlo, n_dev)

        rec.update(
            status="ok",
            n_devices=int(n_dev),
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            memory=mem_rec,
            flops=float(cost.get("flops", 0.0)),
            bytes_accessed=float(cost.get("bytes accessed", 0.0)),
            cost=cost_rec,
            collectives=colls.as_dict(),
            hlo_bytes=len(hlo),
        )
    except Exception as e:  # a failure here is a bug in the system
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    _save(rec, save)
    return rec


def _save(rec: dict, save: bool):
    if not save:
        return
    os.makedirs(ARTIFACTS, exist_ok=True)
    path = os.path.join(
        ARTIFACTS, f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    meshes = []
    if args.multi_pod or not args.single_pod:
        meshes.append(True)
    if args.single_pod or not args.multi_pod:
        meshes.append(False)
    meshes = sorted(set(meshes))  # False (single) first

    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else list(SHAPES)

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "pod2x16x16" if mp else "pod16x16"
                path = os.path.join(
                    ARTIFACTS, f"{arch}__{shape}__{mesh_name}.json")
                if args.skip_existing and os.path.exists(path):
                    rec = json.load(open(path))
                    if rec.get("status") in ("ok", "skipped"):
                        print(f"[cached ] {arch} {shape} {mesh_name} "
                              f"{rec['status']}")
                        results.append(rec)
                        continue
                rec = run_one(arch, shape, mp)
                msg = rec["status"]
                if rec["status"] == "ok":
                    msg += (f" flops={rec['flops']:.3e} "
                            f"coll={rec['collectives']['total_bytes']:.3e}B "
                            f"compile={rec['compile_s']}s")
                elif rec["status"] == "error":
                    msg += f" {rec['error'][:160]}"
                print(f"[{rec['status']:7s}] {arch} {shape} "
                      f"{'pod2x16x16' if mp else 'pod16x16'} {msg}", flush=True)
                results.append(rec)

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\nDRY-RUN SUMMARY: {n_ok} ok, {n_skip} skipped, {n_err} errors "
          f"of {len(results)}")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
