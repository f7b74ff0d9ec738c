"""Checkpointing + fault tolerance: save/restore roundtrip, async saver,
gradient compression with error feedback, straggler detection, elastic
mesh planning, evaluation-campaign deadline handling."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import AsyncCheckpointer, latest_step, restore, save
from repro.core.plopper import DeadlineEvaluator, EvalResult, TimingEvaluator
from repro.ft import (
    LADDER,
    StragglerMonitor,
    compressed_psum,
    ef_compress_grads,
    plan_mesh,
    quantize,
)


def _tree():
    return {
        "w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
        "b": jnp.ones((4,), jnp.bfloat16),
        "nested": {"step": jnp.asarray(7, jnp.int32)},
    }


def test_save_restore_roundtrip(tmp_path):
    t = _tree()
    save(str(tmp_path), t, step=3)
    got, step = restore(str(tmp_path), t)
    assert step == 3
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(t)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_latest_step_and_multiple(tmp_path):
    t = _tree()
    for s in (1, 5, 3):
        save(str(tmp_path), t, step=s)
    assert latest_step(str(tmp_path)) == 5
    _, step = restore(str(tmp_path), t)   # default: latest
    assert step == 5


def test_restore_shape_mismatch_rejected(tmp_path):
    save(str(tmp_path), _tree(), step=1)
    bad = dict(_tree(), w=jnp.zeros((2, 2)))
    with pytest.raises(ValueError, match="mismatch"):
        restore(str(tmp_path), bad)


def test_async_checkpointer_gc(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path), keep=2)
    t = _tree()
    for s in range(5):
        ck.save(t, step=s)
    ck.wait()
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(tmp_path))
    assert steps == [3, 4]


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------


def test_quantize_dequantize_bounded_error():
    x = jnp.asarray(np.random.default_rng(0).standard_normal(1000), jnp.float32)
    q, scale = quantize(x)
    err = np.abs(np.asarray(q, np.float32) * float(scale) - np.asarray(x))
    assert err.max() <= float(scale) * 0.5 + 1e-6


def test_error_feedback_is_unbiased_over_steps():
    """With error feedback the *accumulated* compressed gradient converges to
    the accumulated true gradient (residual stays bounded)."""
    rng = np.random.default_rng(1)
    g_true = jnp.asarray(rng.standard_normal((64,)), jnp.float32) * 0.1
    residual = {"g": jnp.zeros((64,), jnp.float32)}
    acc = jnp.zeros((64,))
    steps = 50
    for _ in range(steps):
        deq, new_r = ef_compress_grads({"g": g_true}, residual)
        residual = {"g": new_r["g"]}
        acc = acc + deq["g"]
    np.testing.assert_allclose(np.asarray(acc / steps), np.asarray(g_true),
                               atol=2e-3)


def test_compressed_psum_in_shard_map():
    devs = jax.devices()
    if len(devs) < 1:
        pytest.skip("no devices")
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(devs[:1]), ("d",))
    x = jnp.linspace(-1.0, 1.0, 16).reshape(1, 16)

    def f(xs):
        return compressed_psum(xs[0], "d")[None]

    out = shard_map(f, mesh=mesh, in_specs=P("d", None), out_specs=P("d", None))(x)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(x[0]), atol=2e-2)


# ---------------------------------------------------------------------------
# straggler + elastic + deadline
# ---------------------------------------------------------------------------


def test_straggler_monitor_flags_slow_steps():
    mon = StragglerMonitor(threshold=2.0, warmup_steps=3)
    for _ in range(10):
        _, slow = mon.observe(0.1)
        assert not slow
    _, slow = mon.observe(0.5)
    assert slow
    assert mon.flagged == 1
    # the straggler does not poison the baseline
    assert mon.ewma < 0.15


def test_elastic_ladder_planning():
    plan = plan_mesh(512)
    assert plan.shape == (2, 16, 16) and plan.multi_pod
    plan = plan_mesh(511)   # one pod lost a chip -> fall to single pod
    assert plan.shape == (16, 16)
    assert plan.dropped == 511 - 256
    plan = plan_mesh(100)
    assert plan.n_devices <= 100
    with pytest.raises(RuntimeError):
        plan_mesh(0)
    # ladder is strictly decreasing in device count
    sizes = [a * b * c for (a, b, c) in LADDER]
    assert sizes == sorted(sizes, reverse=True)


def test_deadline_evaluator_flags_stragglers():
    def slow_eval(cfg):
        time.sleep(0.05)
        return EvalResult(1.0, True, {})

    ev = DeadlineEvaluator(slow_eval, deadline_sec=0.01)
    res = ev({"x": 1})
    assert not res.ok
    assert "straggler_wall_sec" in res.info

    ev2 = DeadlineEvaluator(slow_eval, deadline_sec=10.0)
    assert ev2({"x": 1}).ok


def test_timing_evaluator_catches_exceptions():
    def broken(cfg):
        raise RuntimeError("synthetic compile failure")

    ev = TimingEvaluator(broken)
    res = ev({"x": 1})
    assert not res.ok and res.objective >= 1e9
    assert "synthetic compile failure" in res.info["error"]


def test_compressed_psum_int8_wire_dtype():
    """The int8 path must put int8 on the wire (the compression claim):
    lower a shard_map psum and assert the all-reduce payload dtype."""
    import re

    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("d",))

    def f(xs):
        return compressed_psum(xs[0], "d")[None]

    x = jnp.linspace(-1, 1, 32).reshape(1, 32)
    txt = jax.jit(shard_map(f, mesh=mesh, in_specs=P("d", None),
                            out_specs=P("d", None))).lower(x).compile().as_text()
    ar_lines = [l for l in txt.splitlines() if " all-reduce(" in l and "=" in l]
    payload_dtypes = set()
    for l in ar_lines:
        payload_dtypes.update(re.findall(r"(s8|f32|bf16)\[", l.split(" all-reduce(")[0]))
    # gradient payload rides in s8; the f32 scale agreement is a scalar pmax
    assert "s8" in payload_dtypes, (payload_dtypes, ar_lines)
