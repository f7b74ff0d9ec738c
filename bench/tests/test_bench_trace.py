"""The trace reduction, checked on a trace excerpt recorded on a TPU v5e
(``data/trace_decode_v5e.json``: the end of one batch-32 decode step of
qwen2-0.5b, the idle gap, the start of the next)."""

from __future__ import annotations

import json
import os
import types

import pytest

from bench import flops, trace_reduce

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "trace_decode_v5e.json")


def _profile():
    with open(FIXTURE) as f:
        d = json.load(f)
    ev = lambda e: types.SimpleNamespace(name=e[0], start_ns=e[1], duration_ns=e[2])
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=p["name"], lines=[
            types.SimpleNamespace(name=ln["name"], events=[ev(e) for e in ln["events"]])
            for ln in p["lines"]]) for p in d["planes"]])


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce_profile(_profile())


def test_busy_and_idle_partition_the_window(summary):
    # numbers read off the excerpt by hand: a 3.128038 ms window, of which
    # the union of op intervals covers 1.599711 ms
    assert summary.window_s == pytest.approx(3.128038e-3, abs=1e-12)
    assert summary.busy_s == pytest.approx(1.599711e-3, abs=1e-12)
    idle = sum(summary.idle_by_host.values())
    assert summary.busy_s + idle == pytest.approx(summary.window_s, abs=1e-12)
    # the host sat in a decode step (waiting for its tokens) through the gap
    assert set(summary.idle_by_host) == {"decode_step"}


def test_busy_is_a_union_not_a_sum(summary):
    total = float(summary.durations.sum())
    assert total > summary.busy_s   # the scan's while op holds its body's ops


def test_decode_attention_calls_found_by_operands(summary):
    assert summary.count_of("decode_attention") == 2
    assert summary.count_of("decode_attention", module="serve_step") == 2
    assert summary.seconds_of("decode_attention") == pytest.approx(402.675e-6, abs=1e-12)
    assert summary.count_of("flash_attention") == 0


def test_breakdown_leaves_out_control_flow_and_names_kernels(summary):
    b = summary.breakdown()
    names = [n for n, _ in b["device_ops"]]
    assert "serve_step:decode_attention" in names
    assert not any(n.endswith(":while") for n in names)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    json.dumps(b)


def test_roofline_of_one_call_is_memory_bound():
    # one call of the excerpt: 64 rows (32 sequences x 2 KV heads), 7 query
    # heads each, a 1024-key bucket
    f, b = flops.decode_attention_call(64, 7, 64, 64 * 1024)
    t, bound = flops.least_seconds(f, b, {"bf16_flops_per_s": 197e12,
                                          "hbm_bytes_per_s": 819e9})
    assert bound == "memory" and 1e-5 < t < 3e-5


@pytest.mark.parametrize("name,label", [
    ('%_unknown_.4 = x custom-call(s32[64], bf16[64,7,64], bf16[64,1024,64], '
     'bf16[64,1024,64]), custom_call_target="tpu_custom_call"', "decode_attention"),
    ('%_unknown_ = x custom-call(bf16[2,128,64], bf16[2,128,64], bf16[2,128,64]), '
     'custom_call_target="tpu_custom_call"', "flash_attention"),
    ('%custom-call.6 = x custom-call(), custom_call_target="AllocateBuffer"',
     "AllocateBuffer"),
    ("%fusion.142 = x fusion(), kind=kCustom", "fusion(kCustom)"),
    ("%copy.64 = bf16[32,2,1024,64]{3,2,1,0} copy(bf16[32,2,1024,64] %bitcast.184)",
     "copy"),
])
def test_op_labels(name, label):
    assert trace_reduce.op_label(name, trace_reduce.load_kernel_table()) == label
