"""repro.dispatch: tuning store, shape-signature lookup, runtime dispatch
with its compiled-executable cache, background tuning, and the warm-start
convergence contract (warm campaigns reach a stored optimum in <= 25% of the
cold-start evaluation count)."""

import math
import os

import numpy as np
import pytest

from repro.core import EvalResult, run_search
from repro.core.database import PerformanceDatabase
from repro.core.space import ConfigurationSpace, Ordinal
from repro.dispatch import (
    BackgroundTuner,
    DispatchService,
    TuningRecord,
    TuningStore,
    bucket_signature,
    register,
    resolve,
    shape_signature,
    signature_distance,
    signature_key,
    parse_signature_key,
)


# ---------------------------------------------------------------------------
# signatures
# ---------------------------------------------------------------------------


def test_signature_key_roundtrip():
    sig = ((1200, 1000), (8,))
    assert parse_signature_key(signature_key(sig)) == sig
    assert signature_key(sig) == "1200x1000;8"


def test_signature_from_arrays_and_scalars():
    sig = shape_signature([np.zeros((64, 32)), 8])
    assert sig == ((64, 32), (8,))


def test_signature_distance_log_scale():
    a, b = ((128, 128),), ((256, 256),)
    assert signature_distance(a, a) == 0.0
    assert signature_distance(a, b) == pytest.approx(1.0)  # one doubling per dim
    # incompatible structure -> inf
    assert signature_distance(a, ((128,),)) == math.inf
    # scale-free: same ratio at any magnitude
    assert signature_distance(((8,),), ((16,),)) == pytest.approx(
        signature_distance(((1024,),), ((2048,),)))


def test_bucket_signature_snaps_to_powers():
    assert bucket_signature(((130, 120), (7,))) == ((128, 128), (8,))


def test_signature_distinguishes_bool_flags():
    # causal=True vs causal=False static kwargs must not share store keys
    assert shape_signature([True]) == ((2,),)
    assert shape_signature([False]) == ((1,),)


# ---------------------------------------------------------------------------
# store
# ---------------------------------------------------------------------------


def _rec(kernel="k", dims=(64, 64), backend="host", obj=1.0, **cfg):
    return TuningRecord(kernel=kernel, signature=(tuple(dims),), backend=backend,
                        config=cfg or {"t": 8}, objective=obj)


def test_store_roundtrip_persistence(tmp_path):
    path = str(tmp_path / "store")
    store = TuningStore(path)
    assert store.put(_rec(obj=2.0, t=8))
    assert store.put(_rec(obj=1.0, t=16))        # improvement: accepted
    assert not store.put(_rec(obj=1.5, t=4))     # regression: rejected
    store2 = TuningStore(path)                   # fresh process view
    assert len(store2) == 1
    got = store2.get("k", ((64, 64),), "host")
    assert got.objective == 1.0 and got.config == {"t": 16}


def test_store_cross_instance_refresh(tmp_path):
    path = str(tmp_path / "store")
    a, b = TuningStore(path), TuningStore(path)
    a.put(_rec(obj=3.0))
    assert b.get("k", ((64, 64),), "host") is None  # not yet refreshed
    b.refresh()
    assert b.get("k", ((64, 64),), "host").objective == 3.0


def test_store_compact_keeps_bests_only(tmp_path):
    path = str(tmp_path / "store")
    store = TuningStore(path)
    for obj in (5.0, 3.0, 1.0):
        store.put(_rec(obj=obj, t=int(obj)))
    store.put(_rec(dims=(128, 128), obj=2.0))
    assert store.compact() == 2
    with open(os.path.join(path, "store.jsonl")) as f:
        assert sum(1 for line in f if line.strip()) == 2
    assert TuningStore(path).get("k", ((64, 64),), "host").objective == 1.0


def test_store_append_after_torn_tail_preserves_both(tmp_path):
    path = str(tmp_path / "store")
    store = TuningStore(path)
    store.put(_rec(obj=2.0, t=8))
    with open(os.path.join(path, "store.jsonl"), "a") as f:
        f.write('{"kernel": "k", "sig')        # crashed writer's fragment
    store2 = TuningStore(path)
    assert store2.put(_rec(obj=1.0, t=16))     # must not merge into the tail
    store3 = TuningStore(path)
    assert store3.get("k", ((64, 64),), "host").objective == 1.0


def test_problem_signature_matches_runtime_dispatch():
    """Configs published offline (CLI --store / pallas_tuning) must land on
    the exact signatures dispatch() derives from runtime args."""
    from repro.kernels import ref as R

    C, A, B = R.init_syr2k(48, 32)
    assert R.problem_signature("syr2k", 48, 32) == shape_signature((C, A, B))
    assert R.problem_signature("mm3", 20, 18, 16, 15, 17) == shape_signature(
        R.init_mm3(20, 18, 16, 15, 17))
    assert R.problem_signature("lu", 24) == shape_signature(R.init_lu(24))
    (Ah,) = R.init_heat3d(16)
    assert R.problem_signature("heat3d", 16, 4) == shape_signature([Ah, 4])
    assert R.problem_signature("covariance", 30, 24) == shape_signature(
        R.init_covariance(30, 24))
    assert R.problem_signature("floyd_warshall", 24) == shape_signature(
        R.init_floyd_warshall(24))


def test_store_ingest_database(tmp_path):
    db = PerformanceDatabase(str(tmp_path / "camp"))
    db.add({"t": 4}, 4.0)
    db.add({"t": 32}, 0.5)
    store = TuningStore(str(tmp_path / "store"))
    rec = store.ingest_database(str(tmp_path / "camp"), "k", ((64, 64),), "host")
    assert rec is not None and rec.config == {"t": 32} and rec.n_evals == 2
    assert store.get("k", ((64, 64),), "host").objective == 0.5


# ---------------------------------------------------------------------------
# lookup: exact hit vs nearest neighbor
# ---------------------------------------------------------------------------


def test_resolve_exact_beats_nearest(tmp_path):
    store = TuningStore(str(tmp_path / "s"))
    store.put(_rec(dims=(128, 128), obj=1.0, t=128))
    store.put(_rec(dims=(1024, 1024), obj=1.0, t=1024))
    hit = resolve(store, "k", ((128, 128),), "host")
    assert hit.exact and hit.distance == 0.0 and hit.config == {"t": 128}


def test_resolve_nearest_by_log_distance(tmp_path):
    store = TuningStore(str(tmp_path / "s"))
    store.put(_rec(dims=(128, 128), obj=1.0, t=128))
    store.put(_rec(dims=(1024, 1024), obj=1.0, t=1024))
    near = resolve(store, "k", ((150, 150),), "host")
    assert not near.exact and near.config == {"t": 128}
    far = resolve(store, "k", ((700, 700),), "host")
    assert far.config == {"t": 1024}
    # max_distance bound and backend isolation
    assert resolve(store, "k", ((150, 150),), "host", max_distance=0.1) is None
    assert resolve(store, "k", ((128, 128),), "tpu") is None


# ---------------------------------------------------------------------------
# dispatch service: executable cache + counters
# ---------------------------------------------------------------------------

_TOY_SEQ = (1, 2, 4, 8, 16, 32)


def _toy_space(target="host", seed=1234):
    cs = ConfigurationSpace(seed=seed)
    cs.add_hyperparameter(Ordinal("s", _TOY_SEQ, default=1))
    return cs


def _toy_evaluator(cfg):
    # minimized at the largest scale factor (deterministic, no timing noise)
    return EvalResult(1.0 / cfg["s"], True, {})


register("toy_scale", builder=lambda cfg: lambda x: x * cfg["s"],
         space=_toy_space, make_evaluator=lambda factory: _toy_evaluator)


def _fragile_builder(cfg):
    # build-time failure mode: a poisoned config raises in the builder
    if cfg["s"] < 0:
        raise ValueError("poisoned config")

    def fn(x):
        # trace-time failure mode: the heat3d `assert total % h == 0` analog
        assert x.shape[0] % cfg["s"] == 0, "indivisible block"
        return x * cfg["s"]

    return fn


register("toy_fragile", builder=_fragile_builder, space=_toy_space)


def test_dispatch_exec_cache_hit_miss(tmp_path):
    store = TuningStore(str(tmp_path / "s"))
    store.put(TuningRecord("toy_scale", ((4,),), "host", {"s": 2}, 0.5))
    svc = DispatchService(store)
    x = np.arange(4.0)
    fn = svc.dispatch("toy_scale", x)
    np.testing.assert_array_equal(np.asarray(fn(x)), x * 2)
    assert svc.stats["exec_miss"] == 1 and svc.stats["exec_hit"] == 0
    assert svc.dispatch("toy_scale", x) is fn           # same shape: cache hit
    assert svc.stats["exec_hit"] == 1
    svc.dispatch("toy_scale", np.arange(8.0))           # new shape: miss
    assert svc.stats["exec_miss"] == 2
    # the repeat dispatch went through the signature fast map: no second
    # store resolution on the hot path
    assert svc.stats["store_exact"] == 1 and svc.stats["store_near"] == 1


def test_dispatch_default_config_without_store():
    svc = DispatchService()
    x = np.arange(4.0)
    np.testing.assert_array_equal(np.asarray(svc.call("toy_scale", x)), x * 1)
    assert svc.stats["store_default"] == 1


def test_dispatch_unseen_shape_uses_nearest(tmp_path):
    store = TuningStore(str(tmp_path / "s"))
    store.put(TuningRecord("toy_scale", ((100,),), "host", {"s": 4}, 0.5))
    svc = DispatchService(store)
    x = np.arange(96.0)   # absent from the store -> nearest (100,) wins
    np.testing.assert_array_equal(np.asarray(svc.call("toy_scale", x)), x * 4)
    assert svc.stats["store_near"] == 1


def test_invalidate_hot_swaps_new_config(tmp_path):
    store = TuningStore(str(tmp_path / "s"))
    store.put(TuningRecord("toy_scale", ((4,),), "host", {"s": 2}, 0.5))
    svc = DispatchService(store)
    x = np.arange(4.0)
    np.testing.assert_array_equal(np.asarray(svc.call("toy_scale", x)), x * 2)
    store.put(TuningRecord("toy_scale", ((4,),), "host", {"s": 8}, 0.1))
    assert svc.invalidate("toy_scale", ((4,),)) == 1
    np.testing.assert_array_equal(np.asarray(svc.call("toy_scale", x)), x * 8)


def test_jit_cached_shares_entry():
    svc = DispatchService()
    f1 = svc.jit_cached("serve/m", lambda x: x + 1)
    f2 = svc.jit_cached("serve/m", lambda x: x + 1)
    assert f1 is f2
    assert svc.stats["exec_miss"] == 1 and svc.stats["exec_hit"] == 1


# ---------------------------------------------------------------------------
# warm start: the <= 25%-of-cold-start convergence contract
# ---------------------------------------------------------------------------


def _quadratic_space(seed=1234):
    cs = ConfigurationSpace(seed=seed)
    vals = tuple(range(16))
    cs.add_hyperparameter(Ordinal("x", vals, default=0))
    cs.add_hyperparameter(Ordinal("y", vals, default=0))
    return cs


def _quadratic_eval(cfg):
    # deterministic toy landscape, optimum at (11, 3)
    return EvalResult((cfg["x"] - 11) ** 2 + (cfg["y"] - 3) ** 2 + 1.0, True, {})


def _evals_to_reach(db, target):
    for r in db.records:
        if r.status == "ok" and r.objective <= target * (1 + 1e-9):
            return r.index + 1
    return None


def test_warm_start_converges_in_quarter_of_cold(tmp_path):
    cold = run_search(_quadratic_space(), _quadratic_eval, max_evals=40,
                      learner="RF", seed=7, n_initial=10)
    stored_obj = cold.best.objective
    cold_evals = _evals_to_reach(cold.db, stored_obj)
    assert cold_evals is not None and cold_evals >= 4, (
        f"landscape too easy for the contract to be meaningful ({cold_evals})")

    # publish the cold campaign into a store, then warm-start a fresh one
    store = TuningStore(str(tmp_path / "s"))
    store.put(TuningRecord("quad", ((16, 16),), "host",
                           dict(cold.best.config), stored_obj, n_evals=40))
    hit = resolve(store, "quad", ((16, 16),), "host")
    warm = run_search(_quadratic_space(), _quadratic_eval, max_evals=40,
                      learner="RF", seed=8, n_initial=10,
                      warm_start=[dict(hit.config)],
                      warm_start_records=[(dict(hit.config), stored_obj)])
    warm_evals = _evals_to_reach(warm.db, stored_obj)
    assert warm_evals is not None
    assert warm_evals <= max(1, cold_evals // 4), (
        f"warm start took {warm_evals} evals vs cold {cold_evals}")


def test_warm_start_records_shrink_init_phase():
    from repro.core.search import BayesianSearch

    space = _quadratic_space()
    priors = [({"x": 11, "y": 3}, 1.0), ({"x": 10, "y": 3}, 2.0),
              ({"x": 11, "y": 4}, 2.0)]
    s = BayesianSearch(space, n_initial=10, prior_records=priors)
    assert s.n_priors == 3 and s.n_initial == 7
    X, y = s._training_data()
    assert X.shape[0] == 3 and y.min() == 1.0  # priors alone seed the surrogate
    # foreign configs are skipped, not fatal
    s2 = BayesianSearch(space, n_initial=10,
                        prior_records=[({"zz": 1}, 1.0)] + priors[:1])
    assert s2.n_priors == 1


# ---------------------------------------------------------------------------
# background tuning
# ---------------------------------------------------------------------------


def test_background_tuner_publishes_and_hot_swaps(tmp_path):
    store = TuningStore(str(tmp_path / "s"))
    tuner = BackgroundTuner(store, max_workers=1, max_evals=8, n_initial=3)
    try:
        fut = tuner.submit("toy_scale", ((4,),), "host",
                           space=_toy_space(), evaluator=_toy_evaluator)
        assert fut is not None
        # duplicate key while in flight (or queued) is deduplicated
        recs = tuner.drain()
        assert tuner.errors == []
        assert recs[0] is not None and recs[0].config["s"] == max(_TOY_SEQ)
        got = store.get("toy_scale", ((4,),), "host")
        assert got is not None and got.source == "background"
    finally:
        tuner.shutdown()


def test_background_tuner_warm_starts_from_neighbors(tmp_path):
    store = TuningStore(str(tmp_path / "s"))
    store.put(TuningRecord("toy_scale", ((8,),), "host", {"s": 32}, 1 / 32))
    tuner = BackgroundTuner(store, max_workers=1, max_evals=3, n_initial=1)
    try:
        tuner.submit("toy_scale", ((4,),), "host",
                     space=_toy_space(), evaluator=_toy_evaluator)
        recs = tuner.drain()
        assert tuner.errors == []
        # with only 3 evals, the neighbor's optimal config was re-evaluated
        # first and wins
        assert recs[0] is not None and recs[0].config["s"] == 32
    finally:
        tuner.shutdown()


# ---------------------------------------------------------------------------
# hardening: poisoned store records, _fast TTL sweep
# ---------------------------------------------------------------------------


def test_poisoned_builder_config_degrades_to_default(tmp_path):
    store = TuningStore(str(tmp_path / "s"))
    store.put(TuningRecord("toy_fragile", ((4,),), "host", {"s": -3}, 0.5))
    svc = DispatchService(store)
    x = np.arange(4.0)
    out = svc.call("toy_fragile", x)               # must not raise
    np.testing.assert_array_equal(np.asarray(out), x * 1)  # default config
    assert svc.stats["build_failed"] == 1
    # the offending record is quarantined: not served again, not re-accepted
    assert store.get("toy_fragile", ((4,),), "host") is None
    assert not store.put(TuningRecord("toy_fragile", ((4,),), "host", {"s": -3}, 0.1))
    # and the quarantine is visible to a fresh process view of the store
    assert not TuningStore(str(tmp_path / "s")).put(
        TuningRecord("toy_fragile", ((4,),), "host", {"s": -3}, 0.01))


def test_poisoned_trace_config_degrades_to_default(tmp_path):
    # builder succeeds but tracing fails (heat3d's indivisible fuse_t analog)
    store = TuningStore(str(tmp_path / "s"))
    store.put(TuningRecord("toy_fragile", ((4,),), "host", {"s": 3}, 0.5))
    svc = DispatchService(store)
    x = np.arange(4.0)
    np.testing.assert_array_equal(np.asarray(svc.call("toy_fragile", x)), x * 1)
    assert svc.stats["build_failed"] == 1
    # a good config for the same key is still accepted after the quarantine
    assert store.put(TuningRecord("toy_fragile", ((4,),), "host", {"s": 2}, 0.4))
    svc.invalidate("toy_fragile")
    np.testing.assert_array_equal(np.asarray(svc.call("toy_fragile", x)), x * 2)
    assert svc.stats["build_failed"] == 1          # no new failure


def test_near_miss_build_failure_does_not_quarantine(tmp_path):
    # a neighbor that fails for THIS shape may be perfectly valid for its
    # own signature — it must degrade to the default without being banned
    store = TuningStore(str(tmp_path / "s"))
    store.put(TuningRecord("toy_fragile", ((6,),), "host", {"s": 3}, 0.5))
    svc = DispatchService(store)
    x = np.arange(4.0)                 # nearest ((6,),): 3 doesn't divide 4
    np.testing.assert_array_equal(np.asarray(svc.call("toy_fragile", x)), x * 1)
    assert svc.stats["build_failed"] == 1
    assert store.get("toy_fragile", ((6,),), "host") is not None
    x6 = np.arange(6.0)                # still serves its own signature
    np.testing.assert_array_equal(np.asarray(svc.call("toy_fragile", x6)), x6 * 3)


def test_quarantine_canonicalizes_on_bucketed_store(tmp_path):
    store = TuningStore(str(tmp_path / "s"), bucket=True)
    store.put(_rec(dims=(130, 120), obj=1.0, t=8))
    store.quarantine(_rec(dims=(130, 120), obj=1.0, t=8))  # raw, unbucketed sig
    assert store.get("k", ((130, 120),), "host") is None
    assert not store.put(_rec(dims=(127, 126), obj=0.1, t=8))  # same bucket: banned


def test_fast_map_sweeps_expired_entries():
    svc = DispatchService(resolve_ttl_sec=0.0, fast_sweep_size=4)
    for i in range(16):  # jittery serving shapes, all instantly stale
        svc.dispatch("toy_scale", np.arange(float(i + 1)))
    # without the sweep the TTL map would hold all 16 signatures
    assert len(svc._fast) <= 5


def test_fast_map_expired_entry_replaced_on_hit():
    svc = DispatchService(resolve_ttl_sec=0.0)
    x = np.arange(4.0)
    svc.dispatch("toy_scale", x)
    assert len(svc._fast) == 1
    svc.dispatch("toy_scale", x)   # expired on hit: dropped then re-inserted
    assert len(svc._fast) == 1


# ---------------------------------------------------------------------------
# store bucketing + eviction
# ---------------------------------------------------------------------------


def test_bucketed_store_collapses_jittery_shapes(tmp_path):
    store = TuningStore(str(tmp_path / "s"), bucket=True)
    assert store.put(_rec(dims=(130, 120), obj=1.0, t=8))
    assert len(store) == 1
    # jittery neighbors land on (and resolve from) the same power-of-two key
    assert store.get("k", ((127, 130),), "host").config == {"t": 8}
    assert store.get("k", ((128, 128),), "host") is not None
    assert not store.put(_rec(dims=(126, 125), obj=2.0, t=4))  # same bucket, worse
    assert len(store) == 1


def test_compact_ttl_evicts_stale_records(tmp_path):
    import dataclasses
    import time as _time

    store = TuningStore(str(tmp_path / "s"))
    store.put(dataclasses.replace(_rec(dims=(64, 64), obj=1.0),
                                  created=_time.time() - 3600))
    store.put(_rec(dims=(128, 128), obj=1.0))
    assert store.compact(ttl_sec=60) == 1
    assert store.get("k", ((64, 64),), "host") is None
    assert store.get("k", ((128, 128),), "host") is not None


def test_compact_per_kernel_budget_keeps_recently_used(tmp_path):
    store = TuningStore(str(tmp_path / "s"))
    for d in (32, 64, 128):
        store.put(_rec(dims=(d, d), obj=1.0))
    store.put(_rec(kernel="other", dims=(8, 8), obj=1.0))
    store.get("k", ((64, 64),), "host")            # LRU-touch one key
    assert store.compact(max_per_kernel=1) == 2    # one per kernel survives
    assert store.get("k", ((64, 64),), "host") is not None
    assert store.get("k", ((32, 32),), "host") is None
    assert store.get("other", ((8, 8),), "host") is not None


def test_quarantine_survives_compact(tmp_path):
    path = str(tmp_path / "s")
    store = TuningStore(path)
    bad = _rec(dims=(64, 64), obj=1.0, t=8)
    store.put(bad)
    store.quarantine(bad)
    store.put(_rec(dims=(128, 128), obj=1.0, t=4))
    assert store.compact() == 1
    fresh = TuningStore(path)
    assert fresh.get("k", ((64, 64),), "host") is None
    assert not fresh.put(_rec(dims=(64, 64), obj=0.1, t=8))  # still banned


# ---------------------------------------------------------------------------
# model-kernel dispatch: flash attention resolves tuned (bq, bk) by signature
# ---------------------------------------------------------------------------


def _ref_attention(q, k, v, causal=True):
    import jax
    import jax.numpy as jnp

    s = jnp.einsum("bqh,bsh->bqs", q, k) * (q.shape[-1] ** -0.5)
    if causal:
        Sq, Sk = q.shape[1], k.shape[1]
        mask = np.arange(Sq)[:, None] >= np.arange(Sk)[None, :]
        s = jnp.where(mask[None], s, -1e30)
    return jnp.einsum("bqs,bsh->bqh", jax.nn.softmax(s, axis=-1), v)


def test_flash_dispatch_resolves_tuned_blocks_by_signature(tmp_path):
    from repro.kernels.model_kernels import (
        flash_attention_signature,
        init_flash_attention,
    )

    q, k, v = init_flash_attention(2, 32, 32, 8)
    ref = np.asarray(_ref_attention(q, k, v))

    svc = DispatchService()                        # empty store -> space default
    out = np.asarray(svc.call("flash_attention", q, k, v, causal=True))
    assert svc.stats["store_default"] == 1
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    store = TuningStore(str(tmp_path / "s"))
    store.put(TuningRecord(
        "flash_attention", flash_attention_signature(2, 32, 32, 8), "host",
        {"impl": "pallas", "bq": 16, "bk": 16}, 0.5))
    svc2 = DispatchService(store)
    out2 = np.asarray(svc2.call("flash_attention", q, k, v, causal=True))
    assert svc2.stats["store_exact"] == 1          # resolved by signature
    assert svc2.stats["build_failed"] == 0         # tuned pallas variant ran
    np.testing.assert_allclose(out2, ref, atol=1e-5, rtol=1e-5)


def test_matmul_dispatch_matches_reference(tmp_path):
    from repro.kernels.model_kernels import init_matmul

    a, b = init_matmul(48, 40, 56)
    store = TuningStore(str(tmp_path / "s"))
    store.put(TuningRecord("matmul", (tuple(a.shape), tuple(b.shape)), "host",
                           {"bm": 16, "bn": 16, "bk": 16, "pack": True}, 0.5))
    svc = DispatchService(store)
    out = np.asarray(svc.call("matmul", a, b))
    assert svc.stats["store_exact"] == 1
    np.testing.assert_allclose(out, np.asarray(a) @ np.asarray(b),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# warm-start accounting fixes
# ---------------------------------------------------------------------------


def test_warm_start_excludes_reevaluated_config_from_priors(tmp_path):
    store = TuningStore(str(tmp_path / "s"))
    store.put(TuningRecord("toy_scale", ((8,),), "host", {"s": 32}, 1 / 32))
    store.put(TuningRecord("toy_scale", ((16,),), "host", {"s": 16}, 1 / 16))
    store.put(TuningRecord("toy_scale", ((64,),), "host", {"s": 8}, 1 / 8))
    tuner = BackgroundTuner(store, max_workers=1, warm_neighbors=3)
    try:
        cfgs, recs = tuner._warm_start("toy_scale", ((8,),), "host")
        assert cfgs == [{"s": 32}]                 # nearest, re-evaluated live
        # the re-evaluated config must NOT also appear as a virtual observation
        assert {"s": 32} not in [c for c, _ in recs]
        assert [c for c, _ in recs] == [{"s": 16}, {"s": 8}]
    finally:
        tuner.shutdown()


def test_warm_start_single_record_yields_no_priors(tmp_path):
    store = TuningStore(str(tmp_path / "s"))
    store.put(TuningRecord("toy_scale", ((8,),), "host", {"s": 32}, 1 / 32))
    tuner = BackgroundTuner(store, max_workers=1)
    try:
        cfgs, recs = tuner._warm_start("toy_scale", ((8,),), "host")
        assert cfgs == [{"s": 32}] and recs is None
    finally:
        tuner.shutdown()


def test_run_search_warm_start_stops_at_budget():
    calls = []

    def ev(cfg):
        calls.append(dict(cfg))
        return EvalResult(1.0 / cfg["s"], True, {})

    warm = [{"s": s} for s in _TOY_SEQ]            # more configs than budget
    res = run_search(_toy_space(), ev, max_evals=2, learner="RF",
                     n_initial=1, warm_start=warm)
    assert len(calls) == 2 and len(res.db) == 2


def test_dispatch_miss_enqueues_background_campaign(tmp_path):
    store = TuningStore(str(tmp_path / "s"))
    tuner = BackgroundTuner(store, max_workers=1, max_evals=6, n_initial=2)
    svc = DispatchService(store, tuner=tuner)
    try:
        x = np.arange(4.0)
        svc.call("toy_scale", x)                  # miss -> default + enqueue
        assert svc.stats["bg_enqueued"] == 1
        svc.call("toy_scale", x)
        assert svc.stats["bg_enqueued"] == 1      # deduplicated while pending
        tuner.drain()
        assert tuner.errors == []
        np.testing.assert_array_equal(             # hot-swapped tuned config
            np.asarray(svc.call("toy_scale", x)), x * max(_TOY_SEQ))
    finally:
        tuner.shutdown()


def test_fast_hit_takes_lock_once():
    """The dispatch fast path (recent resolution, warm executable) must cost
    exactly one lock acquisition — read, exec lookup, and stat bump share a
    single critical section — even with metrics enabled: metric recording is
    shard-local (lock-free after the shard's one-time registration), so the
    registry lock must see ZERO acquisitions on the fast hit."""
    import threading

    from repro.obs.metrics import MetricsRegistry

    svc = DispatchService(metrics=MetricsRegistry())
    x = np.arange(4.0)
    svc.dispatch("toy_scale", x)  # populate the fast map + executable cache
    # (and register this thread's metrics shard — a one-time cost)

    class CountingLock:
        def __init__(self, inner):
            self._inner = inner
            self.acquisitions = 0

        def __enter__(self):
            self.acquisitions += 1
            return self._inner.__enter__()

        def __exit__(self, *exc):
            return self._inner.__exit__(*exc)

    counting = CountingLock(threading.RLock())
    svc._lock = counting
    reg_counting = CountingLock(threading.Lock())
    svc.metrics._lock = reg_counting
    hits_before = svc.stats["exec_hit"]
    svc.dispatch("toy_scale", x)
    assert svc.stats["exec_hit"] == hits_before + 1
    assert counting.acquisitions == 1
    assert reg_counting.acquisitions == 0
    # ...and the recording really happened: the fast-hit counter folded at
    # snapshot time shows this dispatch
    snap = svc.metrics.snapshot()
    fast = [c for c in snap["counters"]
            if c["name"] == "dispatch_requests_total"
            and c["labels"].get("path") == "fast_hit"]
    assert fast and fast[0]["value"] >= 1.0


def test_telemetry_reports_execute_latency_quantiles():
    """telemetry() surfaces per-signature execute-latency p50/p99 from the
    dispatch_execute_seconds histogram; the flat legacy keys stay intact."""
    from repro.obs.metrics import MetricsRegistry

    svc = DispatchService(metrics=MetricsRegistry())
    x = np.arange(4.0)
    fn = svc.dispatch("toy_scale", x)
    for _ in range(5):
        fn(x)
    tel = svc.telemetry()
    assert "exec_hit" in tel and "store_default" in tel  # legacy shape intact
    lat = tel["execute_latency"]
    assert len(lat) == 1
    row = lat[0]
    assert row["kernel"] == "toy_scale"
    assert row["backend"] == svc.backend
    assert row["count"] == 5
    assert 0 < row["p50_sec"] <= row["p99_sec"]
    assert row["mean_sec"] > 0


def test_kernel_under_jit_records_no_execute_latency():
    """A dispatched kernel called inside a jitted function runs at trace
    time only: it adds no ``dispatch_execute_seconds`` observation and no
    ``dispatch.execute`` span. An eager call adds one of each."""
    import jax

    from repro.obs.metrics import MetricsRegistry, summarize_histograms
    from repro.obs.trace import recorded_spans

    svc = DispatchService(metrics=MetricsRegistry())
    x = np.arange(4.0)
    fn = svc.dispatch("toy_scale", x)

    def observed():
        rows = summarize_histograms(svc.metrics.snapshot(),
                                    name="dispatch_execute_seconds")
        return sum(r["count"] for r in rows)

    def execute_spans():
        return sum(s.name == "dispatch.execute" and s.attrs.get("kernel") == "toy_scale"
                   for s in recorded_spans())

    spans0 = execute_spans()
    out = jax.jit(lambda v: fn(v) + 1.0)(x)
    np.testing.assert_array_equal(np.asarray(out), x + 1.0)
    assert observed() == 0 and execute_spans() == spans0
    np.testing.assert_array_equal(np.asarray(fn(x)), x)
    assert observed() == 1 and execute_spans() == spans0 + 1


def test_optimizer_overhead_telemetry_flows_to_tuner(tmp_path):
    """Campaign.timings (ask/tell/wait seconds) aggregate into
    BackgroundTuner.stats — the CATBench-style first-class overhead metric."""
    store = TuningStore(str(tmp_path / "s"))
    tuner = BackgroundTuner(store, max_workers=1, max_evals=5, n_initial=2)
    svc = DispatchService(store, tuner=tuner)
    try:
        svc.dispatch("toy_scale", np.arange(4.0))
        tuner.drain()
        assert tuner.errors == []
        assert tuner.stats["campaigns"] == 1
        assert tuner.stats["ask_sec"] > 0.0
        assert tuner.stats["tell_sec"] > 0.0
    finally:
        tuner.shutdown()
