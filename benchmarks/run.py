"""Benchmark harness entry point — one function per paper table.

Prints ``name,us_per_call,derived`` CSV. Knobs (env):
  REPRO_BENCH_EVALS    autotuning campaign length (default 30; paper: 200)
  REPRO_BENCH_SCALE    small | large dataset sizes
  REPRO_BENCH_LEARNER  surrogate for the per-table campaigns (default RF)
  REPRO_BENCH_ONLY     comma-separated table substring filter
"""

from __future__ import annotations

import os
import sys
import time
import traceback


def main() -> None:
    from benchmarks.common import emit
    from benchmarks.learners import learner_comparison
    from benchmarks.roofline_table import csv_rows
    from benchmarks.tables import ALL_TABLES

    only = [s for s in os.environ.get("REPRO_BENCH_ONLY", "").split(",") if s]

    def wanted(name: str) -> bool:
        return not only or any(o in name for o in only)

    t_start = time.time()
    for table_fn in ALL_TABLES:
        if not wanted(table_fn.__name__):
            continue
        t0 = time.time()
        try:
            rows = table_fn()
            emit(rows)
            print(f"# {table_fn.__name__} took {time.time()-t0:.1f}s",
                  file=sys.stderr)
        except Exception:  # noqa: BLE001 — one broken table must not kill the run
            print(f"{table_fn.__name__}/ERROR,0,{traceback.format_exc(limit=2)!r}")

    if wanted("pallas"):
        try:
            from benchmarks.pallas_tuning import tune_all
            emit(tune_all())
        except Exception:  # noqa: BLE001
            print(f"pallas_tuning/ERROR,0,{traceback.format_exc(limit=2)!r}")

    if wanted("learners"):
        try:
            emit(learner_comparison())
        except Exception:  # noqa: BLE001
            print(f"learners/ERROR,0,{traceback.format_exc(limit=2)!r}")

    if wanted("roofline"):
        emit(csv_rows())

    print(f"# total {time.time()-t_start:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache

    enable_compile_cache()
    main()
