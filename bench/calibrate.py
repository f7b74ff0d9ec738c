"""Readings that a cell's correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20

Runs the cell's driver once per seed in one process (set-up compiles once)
with the control judged in the system's place (for a served model, the
float8 reference's first choices), and prints one JSON line per seed: the
checks and ``correct`` as the control comes out, and both readings, the
system's and the control's. The benchmark's own runs never run the
control. ``--out`` also appends the lines to a file.
"""

import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:1] = [_ROOT, os.path.join(_ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    import argparse
    import json

    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    bench = harness.load_benchmark()
    cell = next(c for c in bench["workloads"] if c["name"] == args.workload)
    config = harness.load_json(os.path.join(harness.BENCH_DIR, "configs",
                                            f"{cell['config']}.json"))
    traffic = harness.load_json(os.path.join(harness.BENCH_DIR, "traffic",
                                             f"{cell['traffic']}.json"))
    try:
        device = harness.device_info(int(cell["chips"]), require_tpu=True)
    except harness.NoDevice as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    driver = harness.load_module(
        os.path.join(harness.BENCH_DIR, "drivers", f"{traffic['driver']}.py"),
        f"bench_driver_{traffic['driver']}")
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        ctx = harness.Context(cell=cell, config=config, traffic=traffic, seed=seed,
                              seconds=args.seconds, trace=False, trace_dir=None,
                              t_process=t, chips=int(cell["chips"]))
        ctx.control = True
        res = driver.run(ctx)
        line = {"workload": cell["name"], "seed": seed, "device": device["kind"],
                "control_correct": res.correct, "attempted": res.attempted,
                "checks": res.checks, "readings": res.records.get("readings"),
                "metrics": res.metrics, "setup_s": res.window_t0 - t,
                "memory_peak_bytes": res.memory_peak_bytes}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
