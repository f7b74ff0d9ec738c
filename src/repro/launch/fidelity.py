"""repro-fidelity — the multi-fidelity cascade's CLI (see :mod:`repro.fidelity`).

    # rank-correlation audit: does the analytic cost model order configs the
    # way measured timing does? Reports Spearman rho per kernel and flags the
    # ones too weak to screen on (screen_ok=false); --strict turns a weak
    # kernel into a non-zero exit (the CI gate)
    python -m repro.launch.fidelity audit [--kernel K] [--samples N] \
        [--rho-min R] [--json] [--out FILE] [--strict]

    # describe a kernel's default cost -> proxy -> hardware ladder: per-rung
    # budgets, promotion counts, and the dims each rung evaluates at
    python -m repro.launch.fidelity show --kernel K [--rung-budgets B0,B1,B2]

The audit measures at the reduced PROXY_DIMS by default so it is cheap
enough to pin in CI; pass --full-dims to audit at bench sizes instead.
"""

from __future__ import annotations

import argparse
import json
import os


def cmd_audit(args) -> int:
    from repro.fidelity.audit import audit_kernel
    from repro.kernels.cost import KERNEL_COST_FNS
    from repro.kernels.problems import BENCH_DIMS

    kernels = [args.kernel] if args.kernel else sorted(KERNEL_COST_FNS)
    rows = [audit_kernel(k, n_samples=args.samples, seed=args.seed,
                         repeats=args.repeats, rho_min=args.rho_min,
                         dims=BENCH_DIMS[k] if args.full_dims else None,
                         target=args.target)
            for k in kernels]
    weak = [r["kernel"] for r in rows if not r["screen_ok"]]
    out = {"rho_min": args.rho_min, "samples": args.samples,
           "seed": args.seed, "audit": rows, "weak_kernels": weak}
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=2)
    if args.json:
        print(json.dumps(out, indent=2))
    else:
        hdr = f"{'kernel':<18} {'rho':>7} {'pairs':>6} {'dropped':>8}  verdict"
        print(hdr)
        print("-" * len(hdr))
        for r in rows:
            rho = "nan" if r["rho"] is None else f"{r['rho']:.3f}"
            verdict = "screen_ok" if r["screen_ok"] else "WEAK"
            print(f"{r['kernel']:<18} {rho:>7} {r['n_paired']:>6} "
                  f"{r['n_dropped']:>8}  {verdict}")
        if weak:
            print(f"weak: {', '.join(weak)} — cost-model ordering below "
                  f"rho_min={args.rho_min}; cascade these over the proxy "
                  f"rung instead of screening analytically")
    return 1 if (args.strict and weak) else 0


def cmd_show(args) -> int:
    from repro.fidelity import default_ladder
    from repro.kernels.problems import BENCH_DIMS, PROXY_DIMS, fidelity_ready

    kernel = args.kernel
    if not fidelity_ready(kernel):
        print(f"{kernel}: fidelity_ready=false (no cost-model entry; "
              f"cannot screen on rung 0)")
        return 1
    budgets = tuple(int(x) for x in args.rung_budgets.split(","))
    ladder = default_ladder(kernel, budgets=budgets)
    print(json.dumps({
        "kernel": kernel,
        "fidelity_ready": True,
        "dims": list(BENCH_DIMS[kernel]),
        "proxy_dims": list(PROXY_DIMS.get(kernel, BENCH_DIMS[kernel])),
        "ladder": ladder.describe(),
    }, indent=2))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="repro-fidelity", description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    aud = sub.add_parser("audit", help="cost-model rank-correlation audit")
    aud.add_argument("--kernel", default=None,
                     help="audit one kernel (default: every fidelity-ready one)")
    aud.add_argument("--samples", type=int, default=10,
                     help="configs sampled per kernel")
    aud.add_argument("--seed", type=int, default=7)
    aud.add_argument("--repeats", type=int, default=1,
                     help="timing repeats per config (min is taken)")
    aud.add_argument("--rho-min", type=float, default=0.2,
                     help="Spearman rho below which a kernel is flagged weak")
    aud.add_argument("--target", default="host", choices=["host", "tpu"],
                     help="config space flavor to sample")
    aud.add_argument("--full-dims", action="store_true",
                     help="measure at bench dims instead of proxy dims")
    aud.add_argument("--json", action="store_true")
    aud.add_argument("--out", default=None, metavar="FILE",
                     help="also write the JSON report to FILE (CI artifact)")
    aud.add_argument("--strict", action="store_true",
                     help="non-zero exit when any kernel is weak (CI gate)")
    aud.set_defaults(fn=cmd_audit)

    sh = sub.add_parser("show", help="describe a kernel's default ladder")
    sh.add_argument("--kernel", required=True)
    sh.add_argument("--rung-budgets", default="64,16,8", metavar="B0,B1,B2")
    sh.set_defaults(fn=cmd_show)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
