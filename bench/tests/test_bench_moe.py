"""CPU rehearsal of the driver that takes the configuration's own reference
(``serve_closed_ref``, with a tiny sparse-expert model) through the
harness; the control in the system's place for the expert model's
reference; and the grouped-matmul readers on a synthetic trace."""

from __future__ import annotations

import json
import os
import types

import numpy as np
import pytest

from bench import flops, flops_moe, harness
from bench.reference import moe_gqa
from bench.tests import rehearsal

MOE = "tiny-moe.tiny-closed-ref"

TINY_MOE = {
    "name": "tiny-moe", "source": "test", "reference": "moe_gqa", "dtype": "float32",
    "hf": {"hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 2,
           "num_attention_heads": 4, "num_key_value_heads": 2, "num_local_experts": 8,
           "num_experts_per_tok": 2, "vocab_size": 256, "rope_theta": 10000.0,
           "rms_norm_eps": 1e-5, "tie_word_embeddings": False},
    "program": {"name": "tiny-moe", "family": "moe", "n_layers": 2, "d_model": 64,
                "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 96, "moe_d_ff": 96,
                "vocab_size": 256, "n_experts": 8, "top_k": 2, "rope_theta": 10000.0,
                "rms_eps": 1e-5, "tie_embeddings": False},
    "reduced": [],
}
TINY_CLOSED_REF = dict(rehearsal.TINY_TRAFFIC, driver="serve_closed_ref")
# the tiny expert model runs in float32: in bf16 a routing near-tie flips
# a token now and then, and how many tokens a CPU window finishes varies
# with the machine's load. At the positions whose router margin is at least
# MOE_MARGIN, the limit lies between the float32 system's widest gaps (0.0
# over 6 seeds on the CPU) and the float8 control's (0.52 to 2.05 on the
# same seeds)
MOE_MARGIN, MOE_LIMIT = 0.1, 0.1
# name: (config, traffic name, traffic, the cell of BENCHMARK.json it reports
# as, limits)
CELLS = {
    MOE: ("tiny-moe", "tiny-closed-ref", TINY_CLOSED_REF, "mixtral-8x7b.gen-closed",
          {"max_gap": {"limit": MOE_LIMIT, "margin": MOE_MARGIN}}),
}


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """rehearsal.bench_copy's copy of the benchmark, with the cells above
    added by data files alone."""
    root = rehearsal.bench_copy(tmp_path_factory.mktemp("bench"), serve_limit=0.05)
    bd = os.path.join(root, "bench")
    _dump(os.path.join(bd, "configs", "tiny-moe.json"), TINY_MOE)
    b = harness.load_benchmark(root)
    for name, (config, traffic, mix, like, limits) in CELLS.items():
        b["workloads"].append({"name": name, "config": config, "traffic": traffic,
                               "chips": 1, "why": "test"})
        for m in b["end_to_end"] + b["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(name)
        _dump(os.path.join(bd, "traffic", f"{traffic}.json"), mix)
        _dump(os.path.join(bd, "limits", f"{name}.json"), limits)
    _dump(os.path.join(root, "BENCHMARK.json"), b)
    return root


def test_expert_model_cell_end_to_end(tpu_branch, root):
    rc, line, err = rehearsal.run(root, MOE, seconds=6.0)
    assert rc == 0 and line["correct"], (line, err)
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert set(line["checks"]) == {"max_gap"}
    assert line["attempted"] >= 2 and line["failed"] == 0


def test_expert_model_cell_traced(tpu_branch, root):
    """Its per-layer metrics read the device trace, which a CPU run has
    not: the traced line leaves them out and the run still ends."""
    rc, line, _ = rehearsal.run(root, MOE, seconds=4.0, trace=1)
    assert rc == 0 and line["correct"]
    assert not {"grouped_matmul_roofline.mixtral-gen",
                "grouped_matmul_ms.mixtral-gen"} & set(line["metrics"])


def test_expert_model_control_is_not_correct(tpu_branch, root):
    """The float8 control of the expert model's reference, judged in the
    system's place, comes out as not correct; the system's own reading,
    taken beside it, is within the limit."""
    res = rehearsal.run_calibration(root, MOE, seconds=6.0)
    got = res.records["readings"]
    assert got["program"]["max_gap"] <= MOE_LIMIT < got["control"]["max_gap"], got
    # the margin keeps a part of the positions, the same for both readings
    assert 0 < got["kept"] < got["tokens"]
    assert got["kept"] == got["kept_by_margin"][str(MOE_MARGIN)]
    assert res.correct is False
    rc, line, _ = rehearsal.run(root, MOE, seconds=6.0, control=True)
    assert rc == 0 and line["correct"] is False, line


# -- the grouped-matmul readers on a synthetic trace --------------------------

def _run(count, seconds, active=(32, 32, 32)):
    dims = moe_gqa.Dims(layers=4, d_model=4096, heads=32, kv_heads=8, head_dim=128,
                        d_ff=14336, experts=8, top_k=2, vocab=32000,
                        rope_theta=1e6, rms_eps=1e-5)
    n = len(active)
    steps = {"t_s": np.arange(n) + 1.0, "t_e": np.arange(n) + 1.5,
             "active": np.asarray(active)}
    trace = types.SimpleNamespace(count_of=lambda kernel, module: count,
                                  seconds_of=lambda kernel, module: seconds)
    rec = {"steps": steps, "dims": dims, "trace_t0": 0.0, "trace_t1": 10.0}
    return harness.Run(cell={}, config={}, traffic={}, seconds=10.0, records=rec,
                       trace=trace, peaks={"bf16_flops_per_s": 197e12,
                                           "hbm_bytes_per_s": 819e9})


def _metric(name):
    return harness.load_module(os.path.join(harness.BENCH_DIR, "metrics", f"{name}.py"),
                               "test_metric_" + name.replace(".", "_").replace("-", "_"))


def test_grouped_matmul_readers_count_every_call():
    roof, ms = _metric("grouped_matmul_roofline.mixtral-gen"), \
        _metric("grouped_matmul_ms.mixtral-gen")
    # 3 steps x 4 layers x 3 calls; each decode call reads all 8 experts'
    # 4096 x 14336 bf16 matrices, 0.94 GB: 1.147 ms at 819 GB/s
    least = 36 * flops.least_seconds(*flops_moe.grouped_matmul_call(64, 4096, 14336, 8),
                                     {"bf16_flops_per_s": 197e12,
                                      "hbm_bytes_per_s": 819e9})[0]
    assert least == pytest.approx(36 * 1.15004e-3, rel=1e-4)
    assert roof.read(_run(36, 2 * least)) == pytest.approx(50.0)
    assert ms.read(_run(36, 0.09)) == pytest.approx(30.0)
    # another unnamed Pallas call in the module: no reading
    assert roof.read(_run(37, 2 * least)) is None and ms.read(_run(37, 0.09)) is None


def test_grouped_matmul_bytes_reach_only_routable_experts():
    one = flops_moe.grouped_matmul_call(2, 4096, 14336, 8)     # one sequence, top-2
    all8 = flops_moe.grouped_matmul_call(64, 4096, 14336, 8)
    assert one[1] == pytest.approx(2 * (2 * 4096 * 14336 + 2 * 4096 + 2 * 14336))
    assert all8[1] > 3.9 * one[1]
