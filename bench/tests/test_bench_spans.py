"""The readers of the program's own spans (``bench/spans.py`` and the
metrics that use it), on synthetic runs whose spans go into the program's
span ring, and the traced CPU rehearsal that prints them."""

from __future__ import annotations

import os

import numpy as np
import pytest

from bench import harness, spans as sp
from bench.tests import rehearsal
from bench.tests.rehearsal import DOCS, SERVE
from bench.trace_reduce import TraceSummary
from repro.obs import trace as obs_trace

T0 = 100.0          # the measured window opens here (host seconds)
NS = 1_000_000_000


def reader(name):
    path = os.path.join(harness.BENCH_DIR, "metrics", f"{name}.py")
    return harness.load_module(path, "bench_metric_" + name.replace(".", "_")).read


@pytest.fixture
def ring():
    fresh = obs_trace.SpanRing()
    old = obs_trace.set_span_ring(fresh)
    try:
        yield fresh
    finally:
        obs_trace.set_span_ring(old)


def put(ring, sid, parent, name, t0, t1, **attrs):
    ring.append((sid, parent, name, round(t0 * NS), round(t1 * NS), 1, attrs))


def make_run(trace=None, seconds=10.0):
    rec = {"t0": T0, "t_end": T0 + seconds, "trace_t0": T0, "trace_t1": T0 + 1.0}
    return harness.Run(cell={}, config={}, traffic={}, seconds=seconds,
                       records=rec, trace=trace, peaks={})


def summary(window_s, ops):
    """A one-device trace whose ops are ``(start, end)`` seconds into it."""
    s = np.array([a for a, _ in ops], float)
    d = np.array([b - a for a, b in ops], float)
    busy = sum(b - a for a, b in ops)
    return TraceSummary(window_s=window_s, busy_s=busy,
                        labels=np.array(["m:op"] * len(ops), dtype=object),
                        starts=s, durations=d, idle_by_host={}, n_devices=1)


def test_admit_compile_ms_unions_each_prefills_jax_descendants(ring):
    # prefill 1: forward holds a compile with a cache load inside it (counts
    # once), and a trace; prefill 2: nothing compiled
    put(ring, 10, 1, "jax.cache_load", T0 + 1.10, T0 + 1.20)
    put(ring, 11, 2, "jax.compile", T0 + 1.05, T0 + 1.25)
    put(ring, 12, 2, "jax.trace", T0 + 1.30, T0 + 1.35)
    put(ring, 2, 1, "serve.prefill.forward", T0 + 1.0, T0 + 1.4)
    put(ring, 1, 0, "serve.prefill", T0 + 1.0, T0 + 1.5, batch=1, prompt_len=16)
    put(ring, 3, 0, "serve.prefill", T0 + 2.0, T0 + 2.1, batch=1, prompt_len=16)
    # a prefill of set-up, before the window, is not read
    put(ring, 5, 0, "serve.prefill", T0 - 1.0, T0 - 0.5)
    put(ring, 6, 5, "jax.compile", T0 - 0.9, T0 - 0.6)
    for name in ("admit_compile_ms.gen", "prefill_compile_ms.docs"):
        assert reader(name)(make_run()) == pytest.approx(1e3 * (0.20 + 0.05) / 2)


def test_step_enqueue_ms_is_the_median_serve_step(ring):
    for i, ms in enumerate((0.2, 0.4, 9.0)):
        put(ring, 20 + i, 0, "serve.step", T0 + i, T0 + i + ms / 1e3, batch=32)
    put(ring, 30, 0, "serve.step", T0 - 2, T0 - 1, batch=32)   # set-up
    assert reader("step_enqueue_ms.gen")(make_run()) == pytest.approx(0.4)


def test_step_compiles_counts_compiles_below_the_decode_path(ring):
    put(ring, 1, 0, "serve.step", T0 + 1.0, T0 + 2.0, batch=32)
    put(ring, 2, 1, "jax.compile", T0 + 1.1, T0 + 1.2)       # a step retrace
    put(ring, 3, 0, "kv.view", T0 + 3.0, T0 + 3.5, slots=32, bucket=512)
    put(ring, 4, 3, "jax.trace", T0 + 3.1, T0 + 3.2)
    put(ring, 5, 4, "jax.compile", T0 + 3.2, T0 + 3.3)       # under kv.view
    put(ring, 6, 0, "serve.prefill", T0 + 4.0, T0 + 5.0)
    put(ring, 7, 6, "jax.compile", T0 + 4.1, T0 + 4.2)       # admission: not counted
    assert reader("step_compiles.gen")(make_run()) == 2
    # no decode step in the window: nothing to read
    obs_trace.set_span_ring(obs_trace.SpanRing())
    assert reader("step_compiles.gen")(make_run()) is None


def test_idle_compile_share_on_a_gap_half_covered_by_a_compile(ring):
    # ops fill [0, 0.2] and [0.4, 1.0]: one idle gap of 0.2 s, of which a
    # jax.compile span covers [0.3, 0.5], i.e. half; a cache load nested in
    # it and a trace inside a busy stretch add nothing
    trace = summary(1.0, [(0.0, 0.1), (0.05, 0.2), (0.4, 1.0)])
    put(ring, 1, 0, "serve.prefill", T0 + 0.25, T0 + 0.6)
    put(ring, 2, 1, "jax.cache_load", T0 + 0.32, T0 + 0.36)
    put(ring, 3, 1, "jax.compile", T0 + 0.3, T0 + 0.5)
    put(ring, 4, 1, "jax.trace", T0 + 0.7, T0 + 0.8)
    run = make_run(trace)
    g0, g1 = sp.idle_gaps(run)
    np.testing.assert_allclose([g0, g1], [[T0 + 0.2], [T0 + 0.4]])
    for name in ("idle_compile_share.gen", "idle_compile_share.docs"):
        assert reader(name)(run) == pytest.approx(50.0)
    assert reader("idle_compile_share.gen")(make_run(None)) is None


def test_interval_overlap_matches_a_brute_force_count():
    rng = np.random.default_rng(3)
    a = rng.uniform(0, 10, (40, 2))
    b = rng.uniform(0, 10, (25, 2))
    A = sp.merge(a.min(1), a.max(1))
    B = sp.merge(b.min(1), b.max(1))
    grid = np.linspace(0, 10, 200001)[:-1] + 2.5e-5
    inside = lambda m, x: ((x[:, None] >= m[0]) & (x[:, None] < m[1])).any(1)
    brute = (inside(A, grid) & inside(B, grid)).sum() * 5e-5
    assert sp.covered(A, B) == pytest.approx(brute, abs=1e-3)


def test_readers_give_none_when_the_ring_dropped_inside_the_window():
    small = obs_trace.SpanRing(maxlen=2)
    old = obs_trace.set_span_ring(small)
    try:
        for i in range(3):
            put(small, 1 + i, 0, "serve.step", T0 + i, T0 + i + 1e-4)
        assert small.dropped == 1
        assert reader("step_enqueue_ms.gen")(make_run()) is None
        # a drop before the window opened leaves the window whole
        early = obs_trace.SpanRing(maxlen=2)
        obs_trace.set_span_ring(early)
        put(early, 1, 0, "serve.step", T0 - 5, T0 - 4)
        put(early, 2, 0, "serve.step", T0 - 3, T0 - 2)
        put(early, 3, 0, "serve.step", T0 + 1, T0 + 1 + 2e-4)
        assert reader("step_enqueue_ms.gen")(make_run()) == pytest.approx(0.2)
    finally:
        obs_trace.set_span_ring(old)


def test_readers_give_none_for_a_program_that_records_no_spans(monkeypatch):
    """A traced run of a program without the span ring (the parent of the
    change that added it) reads nothing and raises nothing."""
    monkeypatch.delattr(obs_trace, "recorded_spans")
    run = make_run(summary(1.0, [(0.0, 0.5)]))
    for name in ("admit_compile_ms.gen", "prefill_compile_ms.docs",
                 "step_enqueue_ms.gen", "step_compiles.gen",
                 "idle_compile_share.gen", "idle_compile_share.docs"):
        assert reader(name)(run) is None, name


def test_traced_rehearsal_prints_the_span_metrics(tpu_branch, root):
    """Spans need no TPU: the traced CPU rehearsal of both cells prints the
    four per-layer metrics read from them (the two idle shares need the
    TPU's trace)."""
    rc, line, _ = rehearsal.run(root, SERVE, seconds=4.0, trace=1)
    assert rc == 0 and line["correct"]
    got = line["metrics"]
    assert {"admit_compile_ms.gen", "step_enqueue_ms.gen", "step_compiles.gen"} <= set(got)
    assert got["admit_compile_ms.gen"]["value"] > 0
    assert got["step_enqueue_ms.gen"]["value"] > 0
    assert got["step_compiles.gen"]["value"] == 0    # every shape warmed in set-up
    # long enough that a whole prefill falls inside the window on a loaded host
    rc, line, _ = rehearsal.run(root, DOCS, seconds=10.0, trace=1)
    assert rc == 0 and line["correct"]
    assert line["metrics"]["prefill_compile_ms.docs"]["value"] > 0
