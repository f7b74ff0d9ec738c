"""Shared benchmark machinery: the per-table comparison runner.

Each PolyBench table compares five compilation strategies, mirroring the
paper's rows (mapping documented in DESIGN.md §2):

  row 1  naive          untransformed loop nest       ("gcc -O3")
  row 2  xla_default    one library call, stock XLA   ("clang -O3")
  row 3  blocked_heur   blocked variant, compiler-default heuristic tiles
                        (128^3 MXU-ish)               ("clang -O3 + polly")
  row 4  blocked_paper  blocked variant, the paper's default tiles
                        (96, 2048, 256)               ("polly + pragmas, default tiles")
  row 5  autotuned      blocked variant, best config from a BO campaign
                        over the paper-shaped space   ("polly + pragmas + ytopt")

All rows are wall-clocked on this host via TimingEvaluator (the role the
paper's Core-i7 plays). Dataset sizes are scaled so campaigns finish on CPU;
set REPRO_BENCH_SCALE=large for closer-to-paper sizes and REPRO_BENCH_EVALS
to change the campaign length (default 30; paper used 200).
"""

from __future__ import annotations

import datetime
import json
import os
import platform
import socket
import subprocess
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import TimingEvaluator, autotune
from repro.core.space import ConfigurationSpace
from repro.launch.device import device_info

EVALS = int(os.environ.get("REPRO_BENCH_EVALS", "30"))
SCALE = os.environ.get("REPRO_BENCH_SCALE", "small")
LEARNER = os.environ.get("REPRO_BENCH_LEARNER", "RF")


def bench_meta() -> dict:
    """Provenance stamp shared by every ``BENCH_*.json`` artifact: which
    host/commit/device produced the numbers and when — so two artifacts are
    comparable (or visibly not) without archaeology."""
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or None
    except Exception:  # noqa: BLE001 — no git is fine (tarball checkout)
        sha = None
    dev = device_info()
    return {
        "device_platform": dev["platform"],
        "device_kind": dev["device_kind"],
        "device_count": dev["count"],
        "host": socket.gethostname(),
        "platform": platform.platform(),
        "machine": platform.machine(),
        "python": platform.python_version(),
        "git_sha": sha,
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
    }


def write_bench_json(path: str, payload: dict) -> dict:
    """Stamp ``payload`` with :func:`bench_meta` and write it as JSON;
    returns the stamped dict."""
    out = {"meta": bench_meta(), **payload}
    with open(path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    return out


def time_callable(fn, args, repeats: int = 3, warmup: int = 1) -> float:
    run = jax.jit(fn)
    out = None
    for _ in range(warmup):
        out = run(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = run(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return min(times)


def run_table(
    name: str,
    naive_fn,
    xla_fn,
    args,
    variant_factory,
    space: ConfigurationSpace,
    heur_config: dict,
    paper_config: dict,
    max_evals: int = EVALS,
    learner: str = LEARNER,
    check_against=None,
) -> list[tuple[str, float, str]]:
    """Returns CSV rows (name, us_per_call, derived)."""
    rows = []

    t = time_callable(naive_fn, args)
    rows.append((f"{name}/naive", t * 1e6, "gcc-O3-role"))

    t = time_callable(xla_fn, args)
    rows.append((f"{name}/xla_default", t * 1e6, "clang-O3-role"))

    for label, cfg in (("blocked_heur", heur_config), ("blocked_paper", paper_config)):
        fn, fargs = variant_factory(cfg)
        t = time_callable(fn, fargs)
        rows.append((f"{name}/{label}", t * 1e6, f"config={cfg}"))

    ev = TimingEvaluator(variant_factory, repeats=2, warmup=1)
    res = autotune(space, ev, max_evals=max_evals, learner=learner, seed=1234)
    best = res.best
    rows.append((
        f"{name}/autotuned_{learner}",
        best.objective * 1e6,
        f"at_eval={best.index}/{max_evals};config={best.config}",
    ))

    if check_against is not None:
        fn, fargs = variant_factory(best.config)
        got = jax.jit(fn)(*fargs)
        ok = bool(jnp.allclose(got, check_against, atol=2e-2, rtol=2e-2))
        rows.append((f"{name}/autotuned_correct", float(ok), "allclose-vs-ref"))
    return rows


def emit(rows) -> None:
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
