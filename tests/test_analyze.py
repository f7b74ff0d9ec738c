"""repro.analyze: static config feasibility (accepted implies builds for
every registered kernel; seeded known-bad configs rejected with stable
reason codes; statically-infeasible store records never cost a build) and
the REP101-REP104 concurrency lint — fixtures mirror the real findings
fixed on this tree, and the tree itself must lint clean."""

import json
import textwrap

import jax
import numpy as np
import pytest

from repro.analyze.feasibility import (
    FEASIBLE,
    PositiveIntTiles,
    check_config,
    feasibility_filter,
    kernel_rules,
    register_rules,
)
from repro.analyze.lint import lint_paths, lint_source
from repro.core import EvalResult
from repro.core.search import BayesianSearch
from repro.core.space import ConfigurationSpace, Ordinal
from repro.dispatch import DispatchService, TuningRecord, TuningStore, register
from repro.engine import Campaign
from repro.fleet import Replica
from repro.kernels.problems import BENCH_DIMS, LARGE_SHAPES, bench_problem
from repro.kernels.spaces import KERNEL_SPACES, kernel_space
from repro.launch.analyze import main as analyze_main


# ---------------------------------------------------------------------------
# feasibility: the zero-false-positive property
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kernel", sorted(KERNEL_SPACES))
def test_accepted_sampled_configs_build(kernel):
    """Every config the feasibility pass accepts at bench dims must survive
    the real builder + an abstract trace — accepted implies builds. This is
    the contract that lets the search path prune and DispatchService
    quarantine on static judgment alone."""
    space = kernel_space(kernel, target="host", seed=5)
    rng = np.random.default_rng(5)
    cfgs = [space.default_configuration()] + space.sample_configurations(4, rng)
    factory = bench_problem(kernel)
    dims = BENCH_DIMS[kernel]
    accepted = 0
    for cfg in cfgs:
        if not check_config(kernel, cfg, dims=dims, target="host").ok:
            continue
        fn, args = factory(cfg)
        jax.eval_shape(fn, *args)   # must not raise
        accepted += 1
    assert accepted, "the sampled space produced no accepted configs to audit"


@pytest.mark.parametrize("kernel,cfg,dims,target,code", [
    ("syr2k", {}, BENCH_DIMS["syr2k"], "host", "missing_param:bi"),
    ("syr2k", {"bi": 0, "bj": 64, "bk": 64}, BENCH_DIMS["syr2k"], "host",
     "tile_not_positive:bi"),
    ("syr2k", {"bi": 2.5, "bj": 64, "bk": 64}, BENCH_DIMS["syr2k"], "host",
     "tile_not_int:bi"),
    ("flash_attention", {"impl": "triton", "bq": 128, "bk": 128},
     BENCH_DIMS["flash_attention"], "host", "invalid_choice:impl"),
    ("heat3d", {"bi": 8, "fuse_t": 3}, (40, 8), "host",
     "fuse_indivisible:fuse_t"),
    ("heat3d", {"bi": 8, "fuse_t": 0}, (40, 8), "host",
     "fuse_not_positive:fuse_t"),
    ("flash_attention", {"impl": "xla", "bq": 1024, "bk": 128},
     LARGE_SHAPES["flash_attention"], "cost", "vmem_overflow"),
    ("decode_attention", {"impl": "cuda", "bk": 128, "hg": 1, "page": 128},
     BENCH_DIMS["decode_attention"], "host", "invalid_choice:impl"),
    # the paged layout contract: the signature's seq is the cache bucket,
    # always a whole multiple of the record's page — 48 never divides 128
    ("decode_attention", {"impl": "xla", "bk": 128, "hg": 1, "page": 48},
     BENCH_DIMS["decode_attention"], "host", "page_indivisible:page"),
    # Mosaic's block rule: a 64-wide lane block of a 4096-wide dim
    ("grouped_matmul", {"impl": "pallas", "tm": 128, "tk": 64, "tn": 2048},
     LARGE_SHAPES["grouped_matmul"], "tpu", "lane_misaligned:tk"),
    ("grouped_matmul", {"impl": "pallas", "tm": 128, "tk": 2048, "tn": 2048},
     LARGE_SHAPES["grouped_matmul"], "tpu", "vmem_overflow"),
])
def test_known_bad_configs_rejected_with_stable_codes(
        kernel, cfg, dims, target, code):
    v = check_config(kernel, cfg, dims=dims, target=target)
    assert not v.ok
    assert code in v.reasons
    assert code in v.reason()   # the quarantine-record form


def test_warnings_do_not_reject():
    # the paper's Floyd-Warshall pathology analog: syr2k host tiles from
    # mixed families pad N=240 to lcm(50,128)=3200 — pathological but it
    # builds, so it must warn, not error
    v = check_config("syr2k", {"bi": 50, "bj": 128, "bk": 64},
                     dims=BENCH_DIMS["syr2k"], target="host")
    assert v.ok
    assert "padding_waste" in {f.code for f in v.warnings}


def test_unknown_kernel_is_feasible():
    # kernels with no registered rules (toy test kernels, third-party
    # registrations) are never guessed about
    assert check_config("no_such_kernel", {"whatever": -1}) is FEASIBLE
    assert kernel_rules("no_such_kernel") == ()
    assert feasibility_filter("no_such_kernel") is None


def test_signature_derived_dims_match_explicit_dims():
    from repro.kernels.problems import problem_signature_for

    sig = problem_signature_for("heat3d", "host")
    bad = {"bi": 8, "fuse_t": 3}
    by_sig = check_config("heat3d", bad, signature=sig, target="host")
    by_dims = check_config("heat3d", bad, dims=BENCH_DIMS["heat3d"],
                           target="host")
    assert by_sig.reasons == by_dims.reasons == ("fuse_indivisible:fuse_t",)


def test_feasibility_filter_prunes_errors_keeps_warnings():
    accept = feasibility_filter("syr2k", dims=BENCH_DIMS["syr2k"],
                                target="host")
    assert accept({"bi": 16, "bj": 16, "bk": 16})
    assert accept({"bi": 50, "bj": 128, "bk": 64})   # warn-only: keep
    assert not accept({"bi": 0, "bj": 16, "bk": 16})
    assert not accept({"bj": 16, "bk": 16})          # missing bi


def test_register_rules_appends_then_replaces():
    name = "anlz_custom_kernel"
    try:
        register_rules(name, [PositiveIntTiles("t")])
        assert not check_config(name, {"t": -1}).ok
        register_rules(name, [], replace=True)
        assert check_config(name, {"t": -1}).ok
    finally:
        register_rules(name, [], replace=True)


# ---------------------------------------------------------------------------
# search-path integration: pruning before acquisition scoring
# ---------------------------------------------------------------------------

_SCALES = (1, 2, 4, 8, 16, 32)


def _scale_space(seed=0):
    cs = ConfigurationSpace(seed=seed)
    cs.add_hyperparameter(Ordinal("s", _SCALES, default=1))
    return cs


def test_search_prunes_infeasible_from_acquisition_pool():
    s = BayesianSearch(_scale_space(), n_initial=2, n_candidates=64, seed=3,
                       feasibility=lambda c: c["s"] < 16)
    for i in range(10):
        cfg = s.ask()
        if i >= 2:  # init-phase draws are not model proposals
            assert cfg["s"] < 16
        s.tell(cfg, EvalResult(1.0 / cfg["s"], True, {}))
    assert s.n_pruned > 0


def test_search_feasibility_none_and_accept_all_are_identical():
    a = BayesianSearch(_scale_space(), n_initial=2, seed=7)
    b = BayesianSearch(_scale_space(), n_initial=2, seed=7,
                       feasibility=lambda c: True)
    for _ in range(8):
        ca, cb = a.ask(), b.ask()
        assert ca == cb   # the fixed-seed trajectory contract
        a.tell(ca, EvalResult(float(ca["s"]), True, {}))
        b.tell(cb, EvalResult(float(cb["s"]), True, {}))
    assert b.n_pruned == 0


def test_search_all_infeasible_falls_back_to_raw_pool():
    # a predicate that rejects everything must not strand the optimizer:
    # the raw pool survives and proposals keep flowing
    s = BayesianSearch(_scale_space(), n_initial=2, seed=1,
                       feasibility=lambda c: False)
    for _ in range(6):
        cfg = s.ask()
        assert cfg["s"] in _SCALES
        # distinct objectives, or the model phase never builds a pool
        s.tell(cfg, EvalResult(float(cfg["s"]) + len(s.db), True, {}))
    assert s.n_pruned > 0


def test_campaign_surfaces_n_pruned_in_timings():
    res = Campaign(_scale_space(),
                   lambda c: EvalResult(1.0 / c["s"], True, {}),
                   max_evals=8, n_initial=2, seed=0,
                   feasibility=lambda c: c["s"] < 16).run()
    assert res.timings["n_pruned"] > 0
    res2 = Campaign(_scale_space(),
                    lambda c: EvalResult(1.0 / c["s"], True, {}),
                    max_evals=6, n_initial=2, seed=0).run()
    assert res2.timings["n_pruned"] == 0


# ---------------------------------------------------------------------------
# dispatch integration: static infeasibility never costs a build
# ---------------------------------------------------------------------------

_BUILDS = {"n": 0}


def _counting_builder(cfg):
    _BUILDS["n"] += 1
    return lambda x: x * cfg["t"]


def _anlz_space(target="host", seed=1234):
    cs = ConfigurationSpace(seed=seed)
    cs.add_hyperparameter(Ordinal("t", (1, 2, 4, 8), default=1))
    return cs


register("anlz_toy", builder=_counting_builder, space=_anlz_space)
register_rules("anlz_toy", [PositiveIntTiles("t")], replace=True)


def test_dispatch_skips_build_for_statically_infeasible_record(tmp_path):
    store = TuningStore(str(tmp_path / "s"))
    store.put(TuningRecord("anlz_toy", ((4,),), "host", {"t": -2}, 0.5))
    svc = DispatchService(store)
    x = np.arange(4.0)
    before = _BUILDS["n"]
    np.testing.assert_array_equal(np.asarray(svc.call("anlz_toy", x)), x * 1)
    # the poisoned record was rejected on static judgment: exactly one
    # build happened (the default config), and it counts as "infeasible",
    # not "build_failed" — the two failure modes stay distinguishable
    assert _BUILDS["n"] == before + 1
    assert svc.stats["infeasible"] == 1
    assert svc.stats["build_failed"] == 0
    q = store.quarantines("anlz_toy")
    assert len(q) == 1
    assert q[0]["reason"] == "tile_not_positive:t"
    # quarantined: a repeat dispatch falls straight to the default
    svc2 = DispatchService(store)
    np.testing.assert_array_equal(np.asarray(svc2.call("anlz_toy", x)), x * 1)
    assert svc2.stats["infeasible"] == 0   # nothing left to reject


def test_dispatch_feasible_record_still_serves(tmp_path):
    store = TuningStore(str(tmp_path / "s"))
    store.put(TuningRecord("anlz_toy", ((4,),), "host", {"t": 4}, 0.5))
    svc = DispatchService(store)
    x = np.arange(4.0)
    np.testing.assert_array_equal(np.asarray(svc.call("anlz_toy", x)), x * 4)
    assert svc.stats["infeasible"] == 0


def test_quarantine_reason_surfaces_in_fleet_status(tmp_path):
    store = TuningStore(str(tmp_path / "s"))
    rec = TuningRecord("anlz_toy", ((4,),), "host", {"t": -2}, 0.5)
    store.put(rec)
    store.quarantine(rec, reason="tile_not_positive:t")
    st = Replica(store).status()
    assert [q["reason"] for q in st["quarantined"]] == ["tile_not_positive:t"]
    assert st["quarantined"][0]["kernel"] == "anlz_toy"


def test_quarantine_reason_defaults_empty(tmp_path):
    store = TuningStore(str(tmp_path / "s"))
    rec = TuningRecord("anlz_toy", ((4,),), "host", {"t": -2}, 0.5)
    store.put(rec)
    store.quarantine(rec)   # pre-reason call shape stays valid
    assert [q["reason"] for q in store.quarantines()] == [""]


# ---------------------------------------------------------------------------
# concurrency lint: fixtures mirror the real findings fixed on this tree
# ---------------------------------------------------------------------------


def _codes(src):
    return [f.code for f in lint_source(textwrap.dedent(src))]


def test_lint_rep101_wallclock_duration():
    # the SyncAgent lag-math finding: wall-clock difference as a duration
    bad = """
    import time

    class Agent:
        def lag(self):
            return time.time() - self.last_sync
    """
    assert "REP101" in _codes(bad)
    # the applied fix: a monotonic companion stamp
    good = bad.replace("time.time()", "time.monotonic()")
    assert _codes(good) == []


def test_lint_rep101_from_time_import():
    assert "REP101" in _codes("""
    from time import time

    def age(t0):
        return time() - t0
    """)


def test_lint_rep102_unguarded_mutation():
    # the TuningStore.get LRU-touch finding: self._access written under
    # self._tlock in some methods, bare elsewhere
    bad = """
    import threading

    class Store:
        def __init__(self):
            self._tlock = threading.Lock()
            self._access = {}

        def put(self, k, v):
            with self._tlock:
                self._access[k] = v

        def get(self, k):
            self._access[k] = 1   # unguarded
            return k
    """
    assert "REP102" in _codes(bad)
    good = bad.replace("self._access[k] = 1   # unguarded",
                       "with self._tlock:\n                self._access[k] = 1")
    assert _codes(good) == []


def test_lint_rep102_locked_helpers_inherit_protection():
    # *_locked helpers and private helpers only called under the lock are
    # caller-holds-lock by convention — no finding
    assert _codes("""
    import threading

    class Store:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0

        def bump(self):
            with self._lock:
                self._n += 1
                self._sweep_locked()

        def _sweep_locked(self):
            self._n = 0
    """) == []


def test_lint_rep103_lock_order_inversion():
    # documented order is store -> fleet: acquiring the TuningStore lock
    # while holding the OpLog lock is an inversion
    bad = """
    class Broker:
        def __init__(self, store: TuningStore, oplog: OpLog):
            self.store = store
            self.oplog = oplog

        def publish(self):
            with self.oplog._lock:
                with self.store._lock:
                    pass
    """
    assert "REP103" in _codes(bad)
    good = """
    class Broker:
        def __init__(self, store: TuningStore, oplog: OpLog):
            self.store = store
            self.oplog = oplog

        def publish(self):
            with self.store._lock:
                with self.oplog._lock:
                    pass
    """
    assert _codes(good) == []


def test_lint_rep103_through_method_call():
    # the inversion through a call: any unlinted TuningStore method may take
    # the store's rank-0 lock while the fleet lock is held
    assert "REP103" in _codes("""
    class Broker:
        def __init__(self, store: TuningStore, oplog: OpLog):
            self.store = store
            self.oplog = oplog

        def publish(self, rec):
            with self.oplog._lock:
                self.store.put(rec)
    """)


def test_lint_rep104_unowned_thread():
    bad = """
    import threading

    class Runner:
        def start(self):
            self._t = threading.Thread(target=self._run)
            self._t.start()
    """
    assert "REP104" in _codes(bad)
    assert _codes(bad.replace("target=self._run",
                              "target=self._run, daemon=True")) == []
    # a stop() handler on the owning class also satisfies the rule
    assert _codes(bad + """
        def stop(self):
            self._t.join()
    """) == []


def test_lint_rep105_runloop_swallow():
    bad = """
    import threading

    class Agent:
        def start(self):
            self._t = threading.Thread(target=self._run, daemon=True)
            self._t.start()

        def _run(self):
            while True:
                try:
                    self.step()
                except Exception:
                    pass
    """
    assert "REP105" in _codes(bad)
    # counting the failure is accounting enough: the daemon stays observable
    assert _codes(bad.replace("pass", 'self.stats["errors"] += 1')) == []
    # so is re-raising after cleanup
    assert _codes(bad.replace("pass", "raise")) == []
    # a narrow except is a deliberate decision, not a swallow
    assert _codes(bad.replace("except Exception:", "except ValueError:")) == []


def test_lint_rep105_reaches_helpers_called_from_the_loop():
    bad = """
    import threading

    class Agent:
        def start(self):
            threading.Thread(target=self._run, daemon=True).start()

        def _run(self):
            while True:
                self._tick()

        def _tick(self):
            try:
                self.sync()
            except Exception:
                return None
    """
    assert "REP105" in _codes(bad)


def test_lint_rep105_ignores_loops_outside_threads():
    src = """
    class Loader:
        def load_all(self, paths):
            out = []
            for p in paths:
                try:
                    out.append(self.parse(p))
                except Exception:
                    continue
            return out
    """
    assert _codes(src) == []


def test_lint_rep105_counter_call_accounts():
    src = """
    import threading

    class Agent:
        def start(self):
            threading.Thread(target=self._run, daemon=True).start()

        def _run(self):
            while True:
                try:
                    self.step()
                except Exception:
                    self.registry.add("errors_total")
    """
    assert _codes(src) == []


def test_lint_pragma_allowlists_a_finding():
    src = """
    import time

    class Rec:
        def age(self):
            # lint: allow=REP101 persisted stamps are cross-process wall-clock
            return time.time() - self.created
    """
    assert _codes(src) == []
    # the pragma only silences the named code
    assert "REP101" in _codes(src.replace("allow=REP101", "allow=REP104"))


def test_lint_tree_is_clean():
    """Tier-1 gate: the codebase holds its own documented concurrency
    invariants. New findings must be fixed or explicitly pragma'd."""
    import os

    import repro

    pkg = os.path.dirname(os.path.abspath(repro.__file__))
    findings = lint_paths([pkg])
    assert findings == [], "\n".join(f.format() for f in findings)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_space_json_artifact(tmp_path, capsys):
    out = tmp_path / "space.json"
    rc = analyze_main(["space", "--kernel", "syr2k", "--samples", "16",
                       "--json", "--out", str(out)])
    assert rc == 0
    data = json.loads(out.read_text())
    assert json.loads(capsys.readouterr().out) == data
    kernels = {r["kernel"] for r in data["audit"]}
    targets = {r["target"] for r in data["audit"]}
    assert kernels == {"syr2k"} and targets == {"host", "cost"}
    for row in data["audit"]:
        assert 0.0 <= row["infeasible_fraction"] <= 1.0
        assert row["n_sampled"] == 17   # default config + samples


def test_cli_lint_budget_gates_exit_code(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\n\n"
                   "class A:\n"
                   "    def age(self):\n"
                   "        return time.time() - self.t0\n")
    assert analyze_main(["lint", str(bad)]) == 1
    assert analyze_main(["lint", str(bad), "--max-findings", "1"]) == 0
    capsys.readouterr()
    rc = analyze_main(["lint", str(bad), "--json"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert data["findings"][0]["code"] == "REP101"


def test_cli_lint_clean_tree_exits_zero():
    assert analyze_main(["lint"]) == 0
