"""Cascade-vs-flat benchmark: same answer, half the hardware bill.

The multi-fidelity claim (repro.fidelity) is quantitative: a cascade that
screens on the analytic cost model and promotes only the top-k should reach
an objective within a few percent of a flat single-fidelity BO campaign
while spending at most half the hardware-rung evaluations. This benchmark
measures exactly that, per kernel:

  * **flat** — one ``Campaign`` wall-clocking every proposal at bench dims
    with budget E (the paper's loop);
  * **cascade** — a ``CascadeCampaign`` over the default ladder whose
    hardware rung gets at most E/2.

Both run the same learner/seed; both winners are then re-timed back-to-back
(min of 5 repeats) so the quality comparison is one fair measurement rather
than two campaigns' internal numbers. Results land in ``BENCH_fidelity.json``
(stamped via ``benchmarks.common.bench_meta``) plus an ``repro.obs``
snapshot with the ``fidelity_screened_total`` / ``fidelity_promoted_total``
counters and per-rung campaign latency histograms.

Usage::

    PYTHONPATH=src python benchmarks/fidelity_bench.py            # full
    PYTHONPATH=src python benchmarks/fidelity_bench.py --quick    # CI smoke

Exit is non-zero when any kernel misses the gate (hardware evals over the
--hw-frac budget, or the cascade winner slower than --tol over the flat
winner); --no-check reports without gating.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import write_bench_json  # noqa: E402
from repro.core.plopper import TimingEvaluator  # noqa: E402
from repro.engine import Campaign  # noqa: E402
from repro.fidelity import CascadeCampaign, default_ladder  # noqa: E402
from repro.kernels.problems import bench_problem  # noqa: E402
from repro.kernels.spaces import kernel_space  # noqa: E402
from repro.obs.export import write_snapshot  # noqa: E402
from repro.obs.metrics import (  # noqa: E402
    MetricsRegistry,
    set_registry,
    summarize_histograms,
)


def retime(kernel: str, config: dict, repeats: int = 5) -> float:
    """One fair measurement for a winner config (min of ``repeats``)."""
    timer = TimingEvaluator(bench_problem(kernel), repeats=repeats, warmup=2)
    res = timer(config)
    return float(res.objective) if res.ok else float("inf")


def bench_kernel(kernel: str, flat_evals: int, budgets: tuple,
                 seed: int, learner: str) -> dict:
    space = kernel_space(kernel, target="host", seed=seed)

    flat = Campaign(
        space, TimingEvaluator(bench_problem(kernel), repeats=2, warmup=1),
        max_evals=flat_evals, learner=learner, seed=seed).run()

    ladder = default_ladder(kernel, budgets=budgets)
    cascade = CascadeCampaign(
        kernel_space(kernel, target="host", seed=seed), ladder,
        learner=learner, seed=seed, kernel=kernel).run()

    # back-to-back re-time of both winners: the quality verdict comes from
    # one measurement context, not from each campaign's own noisy numbers
    t_flat = retime(kernel, dict(flat.best.config))
    t_cascade = retime(kernel, dict(cascade.best.config))
    return {
        "kernel": kernel,
        "learner": learner,
        "seed": seed,
        "flat": {
            "budget": flat_evals,
            "hw_evals": flat.n_evaluated + flat.n_failed,
            "best_config": dict(flat.best.config),
            "best_sec": float(flat.best.objective),
            "retimed_sec": t_flat,
        },
        "cascade": {
            "ladder": ladder.describe(),
            "hw_evals": cascade.hw_evals,
            "screened": cascade.stats["screened"],
            "promoted": cascade.stats["promoted"],
            "calibration": cascade.stats["calibration"],
            "best_config": dict(cascade.best.config),
            "best_sec": float(cascade.best.objective),
            "retimed_sec": t_cascade,
        },
        "hw_eval_ratio": round(cascade.hw_evals / max(1, flat.n_evaluated
                                                      + flat.n_failed), 4),
        "quality_ratio": round(t_cascade / t_flat, 4) if t_flat > 0
        else float("inf"),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--kernels", nargs="*", default=["matmul", "mm3"],
                    help="kernels to compare (default: the two whose "
                         "cost-model rank correlation is strongest)")
    ap.add_argument("--flat-evals", type=int, default=30,
                    help="flat campaign budget E (cascade hardware rung "
                         "gets at most E/2)")
    ap.add_argument("--budgets", default=None, metavar="B0,B1[,B2]",
                    help="cascade rung budgets (default: 4E cost screens, "
                         "E/2 proxy, E/2 - 3 hardware)")
    ap.add_argument("--learner", default="RF")
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--tol", type=float, default=0.05,
                    help="allowed cascade slowdown over flat (0.05 = 5%%)")
    ap.add_argument("--hw-frac", type=float, default=0.5,
                    help="max cascade hardware evals as a fraction of flat's")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: flat budget 16, cost->hw ladder (96, 8)")
    ap.add_argument("--no-check", action="store_true",
                    help="report without gating the exit code")
    ap.add_argument("--out", default="BENCH_fidelity.json")
    args = ap.parse_args(argv)

    if args.quick:
        args.flat_evals = 16
        budgets = (96, 8)
    elif args.budgets:
        budgets = tuple(int(x) for x in args.budgets.split(","))
    else:
        e = args.flat_evals
        budgets = (4 * e, max(4, e // 2), max(3, e // 2 - 3))
    if args.budgets and args.quick:
        budgets = tuple(int(x) for x in args.budgets.split(","))

    registry = MetricsRegistry()
    prev = set_registry(registry)  # capture the fidelity counters per run
    try:
        rows = [bench_kernel(k, args.flat_evals, budgets, args.seed,
                             args.learner) for k in args.kernels]
    finally:
        set_registry(prev)

    failures = []
    for r in rows:
        hw_ok = r["hw_eval_ratio"] <= args.hw_frac + 1e-9
        q_ok = r["quality_ratio"] <= 1.0 + args.tol
        r["gate"] = {"hw_ok": hw_ok, "quality_ok": q_ok,
                     "pass": hw_ok and q_ok}
        if not r["gate"]["pass"]:
            failures.append(r["kernel"])
        print(f"[{r['kernel']}] flat {r['flat']['retimed_sec'] * 1e6:.1f}us "
              f"({r['flat']['hw_evals']} hw evals) vs cascade "
              f"{r['cascade']['retimed_sec'] * 1e6:.1f}us "
              f"({r['cascade']['hw_evals']} hw evals, "
              f"{r['cascade']['screened']} screened) "
              f"quality x{r['quality_ratio']:.3f} "
              f"hw x{r['hw_eval_ratio']:.2f} "
              f"{'PASS' if r['gate']['pass'] else 'FAIL'}", flush=True)

    payload = {
        "flat_evals": args.flat_evals,
        "budgets": list(budgets),
        "tol": args.tol,
        "hw_frac": args.hw_frac,
        "kernels": rows,
        "gate_pass": not failures,
        "obs": summarize_histograms(registry.snapshot()),
    }
    write_bench_json(args.out, payload)
    obs_out = os.path.splitext(args.out)[0] + ".obs.jsonl"
    write_snapshot(obs_out, registry=registry, bench="fidelity")
    print(f"wrote {args.out} and {obs_out}")

    if failures and not args.no_check:
        print(f"FAIL: gate missed for {', '.join(failures)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
