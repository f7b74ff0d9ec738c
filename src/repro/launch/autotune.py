"""Autotuning CLI — the paper's ytopt interface (--max-evals / --learner),
now a thin adapter over :class:`repro.engine.Campaign`.

    PYTHONPATH=src python -m repro.launch.autotune --kernel syr2k \
        --max-evals 30 --learner RF --db results/syr2k_rf

--backend host times each configuration on the device this process runs
on. On a TPU that is the Pallas kernel of repro.kernels.ops over the TPU
tile space at the paper's LARGE sizes; anywhere else it is the blocked XLA
mold of repro.kernels.variants over the paper's CPU tile lists at bench
sizes (backend B1). --backend cost scores the TPU tile space with the
analytic model (B2) at LARGE sizes. The exit code is 1 when no evaluation
succeeded.

--parallel N keeps N candidate evaluations in flight (constant-liar
batching over a thread pool); N=1 is the paper's serial loop, bit-for-bit.
--resume requires --db and continues a killed campaign from its JSONL
checkpoint: completed evaluations are never re-run, and the campaign
performs exactly the remaining budget.

--warm-start STORE_DIR seeds the campaign from a repro.dispatch TuningStore:
the store's nearest tuned config (by log-scale shape distance) is evaluated
first and its neighbors seed the surrogate, so a warmed campaign reaches the
prior optimum in a fraction of the cold-start budget. --store STORE_DIR
publishes this campaign's winner back (both flags may name the same dir).

--cascade runs a repro.fidelity multi-fidelity cascade instead of a flat
campaign: a wide pool is screened on the analytic cost model, the top-k
re-timed at reduced proxy dims, and only the survivors measured at full
size (--rung-budgets / --promote shape the ladder). With --db, each rung
checkpoints under <db>/rung<level>/ and --resume continues with exactly the
remaining per-rung budgets.
"""

from __future__ import annotations

import argparse
import json

from repro.core import TimingEvaluator, autotune
from repro.core.findmin import importance_report
from repro.kernels.problems import (
    bench_problem,
    campaign_dims,
    make_cost_evaluator,
    problem_signature_for,
    tpu_problem,
)
from repro.kernels.spaces import KERNEL_SPACES, kernel_space
from repro.kernels.util import default_target


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--kernel", required=True, choices=sorted(KERNEL_SPACES))
    ap.add_argument("--max-evals", type=int, default=100,
                    help="evaluation budget (paper default: 100; paper runs: 200)")
    ap.add_argument("--learner", default="RF", choices=["RF", "ET", "GBRT", "GP"])
    ap.add_argument("--backend", default="host", choices=["host", "cost"])
    ap.add_argument("--db", default=None, help="performance database directory")
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--parallel", type=int, default=1, metavar="N",
                    help="candidate evaluations in flight (1 = serial paper loop)")
    ap.add_argument("--resume", action="store_true",
                    help="continue a killed campaign from --db's JSONL checkpoint")
    ap.add_argument("--warm-start", default=None, metavar="STORE_DIR",
                    help="TuningStore to warm-start from (nearest-neighbor seed)")
    ap.add_argument("--store", default=None, metavar="STORE_DIR",
                    help="TuningStore to publish this campaign's best into")
    ap.add_argument("--prune-infeasible", action="store_true",
                    help="statically prune infeasible candidates from the "
                         "acquisition pool (repro.analyze feasibility rules; "
                         "off by default — pruning changes fixed-seed "
                         "trajectories)")
    ap.add_argument("--cascade", action="store_true",
                    help="multi-fidelity cascade (repro.fidelity): screen on "
                         "the analytic cost model, re-time a reduced proxy "
                         "shape, and spend full timings only on promoted "
                         "top-k configs")
    ap.add_argument("--rung-budgets", default=None, metavar="B0,B1[,B2]",
                    help="per-rung evaluation budgets, bottom-up (2 entries "
                         "= cost->hw, 3 = cost->proxy->hw; default 64,16,8)")
    ap.add_argument("--promote", default=None, metavar="K1[,K2]",
                    help="top-k promoted from each non-top rung "
                         "(default: half the next rung's budget)")
    args = ap.parse_args(argv)

    if args.resume and not args.db:
        ap.error("--resume requires --db (the checkpoint to resume from)")
    if args.cascade and args.backend == "cost":
        ap.error("--cascade needs a timed backend above the analytic model; "
                 "--backend cost IS the cascade's rung 0")
    if (args.rung_budgets or args.promote) and not args.cascade:
        ap.error("--rung-budgets/--promote only apply with --cascade")
    target = default_target()
    if args.cascade and target == "tpu":
        ap.error("--cascade times the host molds at bench sizes; on a TPU "
                 "run the flat campaign, which times the Pallas kernels")

    if args.backend == "cost":
        evaluator = make_cost_evaluator(args.kernel)
        space = kernel_space(args.kernel, target="tpu", seed=args.seed)
    else:
        problem = tpu_problem if target == "tpu" else bench_problem
        evaluator = TimingEvaluator(problem(args.kernel), repeats=2, warmup=1)
        space = kernel_space(args.kernel, target=target, seed=args.seed)

    sig = problem_signature_for(args.kernel, args.backend)
    warm_cfgs, warm_recs = None, None
    if args.warm_start:
        from repro.dispatch import TuningStore
        from repro.dispatch.lookup import warm_start_material
        warm_cfgs, warm_recs = warm_start_material(
            TuningStore(args.warm_start), args.kernel, sig, args.backend)
        if warm_cfgs is not None:
            print(f"warm-start: nearest store config re-evaluated first, "
                  f"{len(warm_recs or [])} neighbor(s) seed the surrogate")
        else:
            print("warm-start: store has no compatible record; cold start")

    if args.resume and not args.cascade:
        from repro.core.database import PerformanceDatabase
        k = len(PerformanceDatabase(args.db).records)
        print(f"resume: {k} record(s) checkpointed, "
              f"{max(0, args.max_evals - k)} evaluation(s) remaining")

    feasibility = None
    if args.prune_infeasible:
        from repro.analyze.feasibility import feasibility_filter
        feasibility = feasibility_filter(
            args.kernel, dims=campaign_dims(args.kernel, args.backend),
            target="cost" if args.backend == "cost" else target)

    cascade_stats = None
    if args.cascade:
        from repro.fidelity import CascadeCampaign, default_ladder

        budgets = tuple(int(x) for x in
                        (args.rung_budgets or "64,16,8").split(","))
        promote = tuple(int(x) for x in args.promote.split(",")) \
            if args.promote else None
        ladder = default_ladder(args.kernel, budgets=budgets, promote=promote)
        if args.resume:
            from repro.core.database import PerformanceDatabase
            import os
            for rung in ladder:
                k = len(PerformanceDatabase(
                    os.path.join(args.db, f"rung{rung.level}")).records)
                print(f"resume: rung {rung.level} ({rung.name}) has {k} "
                      f"record(s), {max(0, rung.budget - k)} remaining")
        cres = CascadeCampaign(
            space, ladder, db_root=args.db, learner=args.learner,
            seed=args.seed, parallel=args.parallel,
            warm_start=warm_cfgs, warm_start_records=warm_recs,
            feasibility=feasibility, kernel=args.kernel).run()
        print(cres.summary())
        res = cres.rungs[-1]   # the hardware rung: the answer + what we publish
        cascade_stats = cres.stats
    else:
        res = autotune(space, evaluator, max_evals=args.max_evals,
                       learner=args.learner, seed=args.seed, db_path=args.db,
                       parallel=args.parallel,
                       warm_start=warm_cfgs, warm_start_records=warm_recs,
                       feasibility=feasibility)
    if feasibility is not None and res.timings:
        print(f"feasibility: pruned {res.timings.get('n_pruned', 0)} "
              f"statically-infeasible candidate(s) from the acquisition pool")

    if res.best is None:
        print(res.summary())
        print("no evaluation succeeded")
        return 1

    if args.store:
        from repro.dispatch import TuningRecord, TuningStore
        TuningStore(args.store).put(TuningRecord(
            kernel=args.kernel, signature=sig, backend=args.backend,
            config=dict(res.best.config), objective=float(res.best.objective),
            n_evals=len(res.db), source=f"cli:{args.db or 'ephemeral'}"))

    print(res.summary())
    out = {
        "best_config": res.best.config,
        "best_objective_sec": res.best.objective,
        "found_at_eval": res.best.index,
        "importance": importance_report(res.db),
    }
    if cascade_stats is not None:
        out["cascade"] = cascade_stats
    print(json.dumps(out, indent=2, default=str))
    return 0


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
