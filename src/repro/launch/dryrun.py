import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST run before any other import (jax locks the
# device count at first init). Everything else follows.

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.configs import ARCHS, SHAPES, cell_supported, get_config   # noqa: E402
from repro.launch.cells import lower_cell, plan_cell                  # noqa: E402
from repro.launch.mesh import make_production_mesh                    # noqa: E402
from repro.perf.roofline import analyze_compiled                      # noqa: E402

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input-shape x mesh) cell, print memory_analysis() and
cost_analysis(), and record roofline terms to a JSON results file.

    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b \
        --shape train_4k --multi-pod

Results append incrementally to --out (crash-safe: rerunning skips done
cells unless --force)."""


def run_cell(arch: str, shape_name: str, multi_pod: bool, knobs: dict | None = None):
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    ok, reason = cell_supported(cfg, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "x".join(map(str, mesh.devices.shape)),
                "status": "skipped", "reason": reason}
    t0 = time.perf_counter()
    plan = plan_cell(arch, shape_name, mesh, knobs)
    lowered, aux = lower_cell(plan, mesh)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    rep = analyze_compiled(compiled, chips=plan.chips,
                           model_flops=aux["model_flops"])
    try:
        mem = compiled.memory_analysis()
        mem_row = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        }
    except Exception:  # noqa: BLE001
        mem_row = {}

    return {
        "arch": arch,
        "shape": shape_name,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "status": "ok",
        "seconds": {"lower": round(t_lower, 1), "compile": round(t_compile, 1)},
        "knobs": aux["knobs"],
        "memory_analysis": mem_row,
        "roofline": rep.row(),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--multi-pod", action="store_true",
                    help="use the (2,16,16) 512-chip mesh")
    ap.add_argument("--both-meshes", action="store_true",
                    help="run each cell on single-pod AND multi-pod meshes")
    ap.add_argument("--all", action="store_true", help="all 40 cells")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--force", action="store_true", help="redo finished cells")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    done: dict = {}
    if os.path.exists(args.out) and not args.force:
        with open(args.out) as f:
            for row in json.load(f):
                done[(row["arch"], row["shape"], row["mesh"])] = row
    results = list(done.values())

    n_fail = 0
    for multi_pod in meshes:
        mesh_name = "2x16x16" if multi_pod else "16x16"
        for arch in archs:
            for shape in shapes:
                key = (arch, shape, mesh_name)
                if key in done:
                    print(f"[skip-done] {key}")
                    continue
                print(f"[dryrun] {arch} x {shape} x {mesh_name} ...", flush=True)
                try:
                    row = run_cell(arch, shape, multi_pod)
                except Exception as e:  # noqa: BLE001
                    row = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "status": "error", "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc(limit=5)}
                    n_fail += 1
                results.append(row)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
                status = row["status"]
                extra = ""
                if status == "ok":
                    r = row["roofline"]
                    extra = (f" dominant={r['dominant']}"
                             f" bound={r['bound_sec']:.4f}s"
                             f" frac={r['roofline_fraction']:.2f}")
                print(f"[{status}] {arch} x {shape} x {mesh_name}{extra}", flush=True)

    print(f"done: {len(results)} rows, {n_fail} failures -> {args.out}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
