"""Shared by the benchmark's CPU tests: a copy of the benchmark with tiny
cells added by data files alone, run through the harness on the CPU with
the system steered onto its TPU branch (Pallas kernels in interpret mode).
"""

from __future__ import annotations

import io
import json
import os
import shutil
import time
import types

from bench import harness

SERVE = "tiny-gqa.tiny-closed"
DOCS = "tiny-gqa.tiny-docs"

TINY_CONFIG = {
    "name": "tiny-gqa", "source": "test", "reference": "dense_gqa",
    "dtype": "bfloat16", "qkv_bias": True,
    "hf": {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
           "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 256,
           "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "tie_word_embeddings": True},
    "program": {"name": "tiny-gqa", "family": "dense", "n_layers": 2, "d_model": 64,
                "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                "vocab_size": 256, "qkv_bias": True, "rope_theta": 10000.0,
                "tie_embeddings": True},
    "reduced": [],
}
TINY_TRAFFIC = {
    "driver": "serve_closed", "clients": 2, "max_batch": 2, "round_cap": 4,
    "prompt_lens": {"min": 8, "max": 16, "count": 2, "spacing": "log"},
    "output_lens": {"min": 16, "max": 32, "count": 2, "spacing": "log_quantiles"},
    "check": {"requests": 4},
}
TINY_DOCS = {
    "driver": "serve_closed", "clients": 2, "max_batch": 2, "round_cap": 4,
    "prompt_lens": {"min": 16, "max": 32, "count": 2, "spacing": "log"},
    "output_lens": {"min": 4, "max": 8, "count": 2, "spacing": "log_quantiles"},
    "check": {"requests": 3},
}
# each tiny cell reports what the named cell of BENCHMARK.json reports
TINY_CELLS = {SERVE: ("tiny-closed", TINY_TRAFFIC, "qwen2-0.5b.gen-closed"),
              DOCS: ("tiny-docs", TINY_DOCS, "qwen2-0.5b.docs-closed")}


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def bench_copy(tmp_path, serve_limit=1.0) -> str:
    """A copy of BENCHMARK.json and bench/ under ``tmp_path`` with throwaway
    cells (a tiny GQA model under tiny closed loops) added as data files
    only."""
    root = str(tmp_path)
    src = os.path.join(harness.ROOT, "bench")
    shutil.copytree(src, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns(".jax_cache", "__pycache__", "tests"))
    b = harness.load_benchmark()
    bd = os.path.join(root, "bench")
    _dump(os.path.join(bd, "configs", "tiny-gqa.json"), TINY_CONFIG)
    for name, (traffic, mix, like) in TINY_CELLS.items():
        b["workloads"].append({"name": name, "config": "tiny-gqa", "traffic": traffic,
                               "chips": 1, "why": "test"})
        for m in b["end_to_end"] + b["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(name)
        _dump(os.path.join(bd, "traffic", f"{traffic}.json"), mix)
        _dump(os.path.join(bd, "limits", f"{name}.json"), {"max_gap": {"limit": serve_limit}})
    _dump(os.path.join(root, "BENCHMARK.json"), b)
    return root


def run(root, workload, *, seed=2**31 + 11, seconds=2.0, trace=0, control=False):
    """Run one cell through the harness on the CPU, the control judged in
    the system's place where ``control``; returns (exit code, parsed last
    stdout line, stderr text)."""
    out, err = io.StringIO(), io.StringIO()
    args = types.SimpleNamespace(workload=workload, seed=seed, seconds=seconds,
                                 trace=trace, control=control)
    rc = harness.run_cell(args, t_process=time.perf_counter(), require_tpu=False,
                          root=root, bench_dir=os.path.join(root, "bench"),
                          out=out, err=err)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()


def run_calibration(root, workload, *, seed=7, seconds=2.0):
    """Run one cell's driver with its control on, as bench/calibrate.py
    does on the chip; returns the driver's result."""
    bd = os.path.join(root, "bench")
    bench = harness.load_benchmark(root)
    cell = next(c for c in bench["workloads"] if c["name"] == workload)
    traffic = harness.load_json(os.path.join(bd, "traffic", f"{cell['traffic']}.json"))
    ctx = harness.Context(
        cell=cell, traffic=traffic, seed=seed, seconds=seconds, trace=False,
        config=harness.load_json(os.path.join(bd, "configs", f"{cell['config']}.json")),
        trace_dir=None, t_process=time.perf_counter(), chips=1, bench_dir=bd)
    ctx.control = True
    driver = harness.load_module(os.path.join(bd, "drivers", f"{traffic['driver']}.py"),
                                 f"bench_driver_{traffic['driver']}")
    return driver.run(ctx)
