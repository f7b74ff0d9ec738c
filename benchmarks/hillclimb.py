"""§Perf hillclimb: drive the dominant roofline term down on chosen cells by
autotuning the distributed-config knob space with the paper's BO engine
(backend B2 objective = compiled-artifact roofline bound, with an HBM-
feasibility penalty).

This is the paper's method applied one level up — the "application/system
parameters" extension its Sec. 5 proposes as future work. Each evaluation is
a full .lower().compile() of the cell on the production mesh + the HLO-walker
roofline; the performance database is the iteration log EXPERIMENTS.md §Perf
reports.

Usage:
  XLA_FLAGS=--xla_force_host_platform_device_count=512 \
  PYTHONPATH=src:. python -m benchmarks.hillclimb --arch qwen2-vl-7b \
      --shape train_4k --evals 12
"""

from __future__ import annotations

import os

# must precede any jax import (jax locks device count at first init)
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=512")

import argparse  # noqa: E402
import json      # noqa: E402

HBM_BYTES = 16e9  # v5e per-chip HBM


def knob_space(kind: str, is_moe: bool, seed: int = 1234):
    from repro.core.space import Categorical, ConfigurationSpace, Ordinal

    cs = ConfigurationSpace(seed=seed)
    if kind == "train":
        cs.add_hyperparameters([
            Ordinal("accum", (1, 2, 4, 8, 16), default=8),
            Categorical("remat", ("none", "dots", "full"), default="full"),
            Ordinal("attn_chunk", (256, 512, 1024, 2048), default=512),
            Categorical("attn_f32", (True, False), default=True),
            Categorical("moment_dtype", ("float32", "bfloat16"),
                        default="float32"),
            Categorical("seq_parallel", (False, True), default=False),
        ])
    else:
        cs.add_hyperparameters([
            Ordinal("attn_chunk", (256, 512, 1024, 2048), default=512),
            Categorical("attn_f32", (True, False), default=True),
            Categorical("mla_absorb", (True, False), default=True),
        ])
    if is_moe:
        cs.add_hyperparameters([
            Ordinal("moe_group", (512, 1024, 2048, 4096, 8192), default=2048),
            Ordinal("capacity_factor", (1.0, 1.25, 1.5, 2.0), default=1.25),
        ])
    return cs


def config_to_knobs(config: dict) -> dict:
    knobs: dict = {}
    overrides: dict = {}
    for k, v in config.items():
        if k in ("attn_f32", "moe_group", "capacity_factor"):
            overrides[k] = v
        elif k == "accum":
            knobs["accum"] = int(v)
        elif k == "attn_chunk":
            knobs["attn_chunk"] = int(v)
        else:
            knobs[k] = v
    if overrides:
        knobs["cfg_overrides"] = overrides
    return knobs


def make_cell_evaluator(arch: str, shape: str, mesh, log: list):
    import jax
    from repro.core.plopper import EvalResult
    from repro.launch.cells import lower_cell, plan_cell
    from repro.perf.roofline import analyze_compiled

    def evaluate(config) -> EvalResult:
        try:
            knobs = config_to_knobs(dict(config))
            plan = plan_cell(arch, shape, mesh, knobs)
            lowered, aux = lower_cell(plan, mesh)
            compiled = lowered.compile()
            rep = analyze_compiled(compiled, chips=plan.chips,
                                   model_flops=aux["model_flops"])
            mem = compiled.memory_analysis()
            dev_bytes = (getattr(mem, "temp_size_in_bytes", 0)
                         + getattr(mem, "argument_size_in_bytes", 0)
                         - getattr(mem, "alias_size_in_bytes", 0))
            obj = rep.bound_sec
            feasible = dev_bytes <= HBM_BYTES
            if not feasible:  # quadratic pressure penalty: OOM-compile analog
                obj = obj * (dev_bytes / HBM_BYTES) ** 2
            row = dict(config=dict(config), objective=obj, feasible=feasible,
                       device_bytes=dev_bytes, **rep.row())
            log.append(row)
            return EvalResult(obj, True, row)
        except Exception as e:  # noqa: BLE001
            log.append(dict(config=dict(config), error=str(e)))
            return EvalResult(1e9, False, {"error": str(e)})

    return evaluate


PROBES = [
    # hypothesis ladder: each row is one lower+compile (see EXPERIMENTS §Perf)
    ("baseline", {}),
    ("seq-parallel residual (activation mem & traffic / model-axis)",
     {"seq_parallel": True}),
    ("seq-parallel + bf16 moments (halve optimizer HBM)",
     {"seq_parallel": True, "moment_dtype": "bfloat16"}),
    ("seq-parallel + bf16 moments + accum 4 (fewer grad passes)",
     {"seq_parallel": True, "moment_dtype": "bfloat16", "accum": 4}),
    ("+ bf16 attention scores",
     {"seq_parallel": True, "moment_dtype": "bfloat16", "attn_f32": False}),
    ("+ tight MoE dispatch (group 1024, cf 1.0)",
     {"seq_parallel": True, "moment_dtype": "bfloat16",
      "moe_group": 1024, "capacity_factor": 1.0}),
]


def run_probe(arch: str, shape: str, out: str, multi_pod: bool = False):
    """Hypothesis -> change -> re-lower -> record, one compile per row."""
    from repro.configs import get_config
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    log: list = []
    ev = make_cell_evaluator(arch, shape, mesh, log)
    space = knob_space("train", is_moe=cfg.n_experts > 0)
    default = space.default_configuration()

    rows = []
    for label, delta in PROBES:
        if ("moe_group" in delta or "capacity_factor" in delta) and not cfg.n_experts:
            continue
        config = dict(default)
        config.update({k: v for k, v in delta.items() if k in default})
        res = ev(config)
        row = dict(log[-1])
        row["hypothesis"] = label
        rows.append(row)
        r = row if "error" not in row else {}
        print(f"  [{label[:52]:52s}] obj={row.get('objective', float('nan')):9.3f}"
              f" mem={r.get('memory_sec', 0):8.3f} coll={r.get('collective_sec', 0):7.3f}"
              f" bytes={r.get('device_bytes', 0)/1e9:6.1f}GB feas={r.get('feasible')}",
              flush=True)

    ok = [r for r in rows if "error" not in r]
    best = min(ok, key=lambda r: r["objective"])
    payload = {"arch": arch, "shape": shape, "mode": "probe",
               "mesh": "x".join(map(str, mesh.devices.shape)),
               "baseline": rows[0], "best": best,
               "improvement": (rows[0]["objective"] - best["objective"])
               / max(rows[0]["objective"], 1e-12),
               "log": rows}
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"[probe] {arch} x {shape}: baseline {rows[0]['objective']:.3f}s -> "
          f"best {best['objective']:.3f}s ({payload['improvement']*100:.1f}%) "
          f"[{best['hypothesis']}]")
    return payload


def run(arch: str, shape: str, evals: int, out: str, multi_pod: bool = False,
        learner: str = "RF", parallel: int = 1, db_path: str | None = None):
    """Thin adapter over :class:`repro.engine.Campaign`: the campaign owns
    warm-start, budget, and (with ``db_path``) crash-safe resume; this
    driver only builds the evaluator and reports the payload. ``parallel``
    keeps that many lower+compile evaluations in flight (each evaluation
    holds the GIL only between XLA calls, so compiles overlap well)."""
    import jax
    from repro.configs import SHAPES, get_config
    from repro.engine import Campaign
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    kind = SHAPES[shape].kind
    log: list = []
    ev = make_cell_evaluator(arch, shape, mesh, log)
    space = knob_space(kind, is_moe=cfg.n_experts > 0)

    # paper-faithful baseline first: the space's defaults, warm-starting the
    # search so 'best' can never regress below the known default schedule
    baseline_cfg = space.default_configuration()
    base = ev(baseline_cfg)
    baseline = dict(log[-1])

    res = Campaign(space, ev, max_evals=evals, learner=learner, seed=1234,
                   n_initial=max(4, evals // 3), parallel=parallel,
                   db_path=db_path, warm_start=[baseline_cfg]).run()
    best = res.best
    payload = {
        "arch": arch, "shape": shape,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "baseline": baseline,
        "best": {"config": best.config, "objective": best.objective,
                 "info": best.info},
        "improvement": (baseline["objective"] - best.objective)
        / max(baseline["objective"], 1e-12),
        "log": log,
    }
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"[hillclimb] {arch} x {shape}: baseline {baseline['objective']:.4f}s"
          f" -> best {best.objective:.4f}s "
          f"({payload['improvement']*100:.1f}% better) config={best.config}")
    return payload


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--evals", type=int, default=12)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--learner", default="RF")
    ap.add_argument("--parallel", type=int, default=1,
                    help="lower+compile evaluations in flight (1 = serial)")
    ap.add_argument("--db", default=None,
                    help="campaign checkpoint dir (resume a killed hillclimb)")
    ap.add_argument("--probe", action="store_true",
                    help="hypothesis-ladder mode: one compile per probe")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    out = args.out or f"results/hillclimb_{args.arch}_{args.shape}.json"
    if args.probe:
        run_probe(args.arch, args.shape, out, args.multi_pod)
    else:
        run(args.arch, args.shape, args.evals, out, args.multi_pod,
            args.learner, parallel=args.parallel, db_path=args.db)


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache

    enable_compile_cache()
    main()
