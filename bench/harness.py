"""The benchmark's harness: finds a cell's files by name, runs its driver,
reads its per-layer metrics and prints the result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. The
harness loads ``configs/<config>.json`` and ``traffic/<traffic>.json``; the
traffic file names its driver, ``drivers/<driver>.py``, whose ``run(ctx)``
does set-up, the measured window and the correctness check. Each per-layer
metric is read by ``metrics/<metric>.py``, whose ``read(run)`` returns a
number or ``None`` when the run holds nothing to read. Adding a
configuration, a traffic mix, a driver or a metric is adding a file.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import zlib
from typing import Any

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# a fixed path inside the checkout: the path is part of the cache's key
COMPILE_CACHE_DIR = os.path.join(BENCH_DIR, ".jax_cache")

__all__ = ["BENCH_DIR", "ROOT", "Context", "Run", "DriverResult",
           "NoDevice", "derive_seed", "load_benchmark", "main"]


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def derive_seed(seed: int, stream: str) -> int:
    """A 31-bit seed for one named random stream of a run. ``--seed`` may
    exceed 32 bits, which ``jax.random.key`` would silently truncate; the
    seed sequence mixes every bit of it."""
    ss = np.random.SeedSequence([int(seed), zlib.crc32(stream.encode())])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file by path: metric files carry dots in their names."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def metrics_of(bench: dict, cell: str, per_layer: bool) -> list[dict]:
    """The end-to-end (``per_layer=False``) or per-layer metrics a cell
    reports. A metric without a ``workloads`` key applies to every cell;
    a per-layer metric without one applies where its ``moves`` metric is
    reported."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not per_layer:
        return e2e
    names = {m["name"] for m in e2e}
    out = []
    for m in bench["per_layer"]:
        if (cell in m["workloads"]) if "workloads" in m else (m["moves"] in names):
            out.append(m)
    return out


@dataclasses.dataclass
class DriverResult:
    """What a driver hands back. ``metrics`` holds the end-to-end metrics
    the driver measured itself (everything but ``setup_s``); ``records``
    is whatever its per-layer readers read; ``checks`` maps each number
    compared to ``(value, limit)``, and ``correct`` says whether every one
    is within its limit."""

    window_t0: float
    attempted: int
    failed: int
    metrics: dict[str, float]
    records: dict[str, Any]
    checks: dict[str, tuple[float, float]]
    correct: bool
    memory_peak_bytes: int


@dataclasses.dataclass
class Run:
    """Everything a per-layer metric reader may read."""

    cell: dict
    config: dict
    traffic: dict
    seconds: float
    records: dict
    trace: Any            # trace_reduce.TraceSummary, or None
    peaks: dict           # this device kind's row of peaks.json


class Context:
    """What the harness gives a driver: the cell's data files, the seed,
    the window length, and the profiler switch."""

    def __init__(self, *, cell: dict, config: dict, traffic: dict, seed: int,
                 seconds: float, trace: bool, trace_dir: str | None,
                 t_process: float, chips: int, bench_dir: str = BENCH_DIR):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.bench_dir = bench_dir
        self.seed, self.seconds = int(seed), float(seconds)
        self.trace, self.trace_dir = bool(trace), trace_dir
        self.t_process, self.chips = t_process, chips
        self.trace_t0 = self.trace_t1 = None
        self.trace_pause = 0.0
        # the control (the reference one precision down) is judged in the
        # system's place; the benchmark's own runs never set it
        self.control = False

    def seed_for(self, stream: str) -> int:
        return derive_seed(self.seed, stream)

    def open_window(self) -> float:
        """Start the profiler when tracing, open the ``bench.window``
        annotation, and return the window's start on the host clock. The
        trace covers the first ``trace_seconds`` of the traffic file (the
        whole window where it gives none); the driver calls :meth:`poll`
        at step boundaries and :meth:`close_window` at the end."""
        if self.trace:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # annotations stay; every Python call would not
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._window = jax.profiler.TraceAnnotation("bench.window")
            self._window.__enter__()
            self.trace_t0 = time.perf_counter()
            return self.trace_t0
        return time.perf_counter()

    def poll(self, now: float) -> float:
        """Stop the trace once ``trace_seconds`` have passed. Returns the
        seconds that writing the trace out took, by which the driver moves
        its window's end: the host does nothing else meanwhile."""
        limit = float(self.traffic.get("trace_seconds", self.seconds))
        if self.trace_t0 is not None and self.trace_t1 is None \
                and now - self.trace_t0 >= limit:
            return self.close_window()
        return 0.0

    def close_window(self) -> float:
        if self.trace_t0 is not None and self.trace_t1 is None:
            import jax

            self._window.__exit__(None, None, None)
            self.trace_t1 = time.perf_counter()
            jax.profiler.stop_trace()
            self.trace_pause = time.perf_counter() - self.trace_t1
            return self.trace_pause
        return 0.0

    def annotate(self, name: str):
        """A host span in the profiler's trace (no-op without tracing)."""
        if self.trace_t0 is None or self.trace_t1 is not None:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table["devices"][kind]


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoDevice(f"needs {chips} TPU chip(s); JAX found "
                       f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device, where the backend says."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks)) if peaks else 0


def enable_compile_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    # every program, however quick to compile, is read back next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _fmt_checks(checks: dict) -> list[str]:
    return [f"check {k}: {v:.6g} (limit {lim:.6g})"
            for k, (v, lim) in checks.items()]


def run_cell(args, *, t_process: float, require_tpu: bool = True,
             root: str = ROOT, bench_dir: str = BENCH_DIR,
             out=sys.stdout, err=sys.stderr) -> int:
    bench = load_benchmark(root)
    cell = _by_name(bench["workloads"], args.workload, "workload")
    config = load_json(os.path.join(bench_dir, "configs", f"{cell['config']}.json"))
    traffic = load_json(os.path.join(bench_dir, "traffic", f"{cell['traffic']}.json"))
    chips = int(cell["chips"])
    device = device_info(chips, require_tpu)
    # a CPU rehearsal reads the v5e's peaks; its device numbers mean nothing
    peaks = peaks_for(device["kind"] if require_tpu else "TPU v5 lite")
    if require_tpu:   # a rehearsal on the CPU leaves JAX's settings alone
        enable_compile_cache()
    driver = load_module(os.path.join(bench_dir, "drivers", f"{traffic['driver']}.py"),
                         f"bench_driver_{traffic['driver']}")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    try:
        ctx = Context(cell=cell, config=config, traffic=traffic, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      trace_dir=trace_dir, t_process=t_process, chips=chips,
                      bench_dir=bench_dir)
        ctx.control = bool(getattr(args, "control", False))
        res: DriverResult = driver.run(ctx)
        summary = None
        if args.trace and require_tpu:
            from bench import trace_reduce

            summary = trace_reduce.reduce_dir(trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    metrics: dict[str, dict] = {}
    if args.trace:
        run = Run(cell=cell, config=config, traffic=traffic,
                  seconds=float(args.seconds), records=res.records,
                  trace=summary, peaks=peaks)
        for m in metrics_of(bench, cell["name"], per_layer=True):
            reader = load_module(os.path.join(bench_dir, "metrics", f"{m['name']}.py"),
                                 "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        measured = dict(res.metrics, setup_s=res.window_t0 - t_process)
        for m in metrics_of(bench, cell["name"], per_layer=False):
            if m["name"] not in measured:   # e.g. no gap between two tokens fell in the window
                print(f"bench: {m['name']} had nothing to measure", file=err)
                continue
            metrics[m["name"]] = {"value": float(measured[m["name"]]),
                                  "unit": m["unit"]}

    dev = dict(device, memory_peak_bytes=int(res.memory_peak_bytes))
    line: dict[str, Any] = {"correct": bool(res.correct),
                            "attempted": int(res.attempted),
                            "failed": int(res.failed), "metrics": metrics,
                            "device": dev}
    if args.trace and summary is not None:
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        line["breakdown"] = summary.breakdown()
    line["checks"] = {k: {"value": float(v), "limit": float(lim)}
                      for k, (v, lim) in res.checks.items()}
    for s in _fmt_checks(res.checks):
        print(s, file=err)
    err.flush()
    print(json.dumps(line), file=out)
    out.flush()
    return 0


def parse_args(argv):
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, t_process: float | None = None,
         require_tpu: bool = True) -> int:
    t_process = time.perf_counter() if t_process is None else t_process
    args = parse_args(argv)
    try:
        return run_cell(args, t_process=t_process, require_tpu=require_tpu)
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
