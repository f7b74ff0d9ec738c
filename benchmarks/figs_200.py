"""The paper's Figs 3-6 at full budget: 200-evaluation campaigns on syr2k
under each of the four learners, with best-so-far trajectories (the red line
in the paper's figures) exported to results/fig_syr2k_<learner>.csv.

This is where the GP duplicate-skip phenomenon shows at the paper's own
scale: GP consumes budget on repeat proposals and completes fewer real
evaluations than RF/ET/GBRT (the paper saw 66/200).

    PYTHONPATH=src:. python -m benchmarks.figs_200 [--evals 200]
"""

from __future__ import annotations

import argparse
import csv
import json
import os

from repro.core import TimingEvaluator, compare_learners
from repro.kernels import ref as R
from repro.kernels import variants as V
from repro.kernels.spaces import kernel_space


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--evals", type=int, default=200)
    ap.add_argument("--n", type=int, default=192)
    ap.add_argument("--m", type=int, default=160)
    ap.add_argument("--outdir", default="results")
    args = ap.parse_args()

    problem = R.init_syr2k(args.n, args.m)
    factory = V.syr2k_host(problem)
    ev = TimingEvaluator(factory, repeats=2, warmup=1)
    results = compare_learners(
        kernel_space("syr2k", target="host"), ev, max_evals=args.evals,
        seed=1234)

    os.makedirs(args.outdir, exist_ok=True)
    summary = {}
    for learner, res in results.items():
        traj = res.db.best_trajectory()
        path = os.path.join(args.outdir, f"fig_syr2k_{learner}.csv")
        with open(path, "w", newline="") as f:
            w = csv.writer(f)
            w.writerow(["evaluation", "objective_sec", "best_so_far_sec",
                        "status"])
            for rec, best in zip(res.db.records, traj):
                w.writerow([rec.index, rec.objective, best, rec.status])
        b = res.best
        summary[learner] = {
            "best_sec": b.objective, "found_at_eval": b.index,
            "real_evaluations": res.n_evaluated,
            "skipped_duplicates": res.n_skipped,
            "budget": args.evals, "config": b.config,
        }
        print(f"[{learner:4s}] best={b.objective*1e6:9.1f}us @eval {b.index:3d}  "
              f"real_evals={res.n_evaluated:3d}/{args.evals}  "
              f"skipped_dups={res.n_skipped}")
    with open(os.path.join(args.outdir, "fig_syr2k_summary.json"), "w") as f:
        json.dump(summary, f, indent=1)


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache

    enable_compile_cache()
    main()
