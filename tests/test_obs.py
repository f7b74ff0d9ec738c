"""repro.obs: lock-free sharded metrics folding to exact totals under
concurrency, deterministic (associative + commutative) histogram merges,
Prometheus text exposition, crash-tolerant Chrome-trace JSONL, the span
ring with its parent ids, profiler mirroring and compile-event children,
the HTTP scrape endpoint, and the instrumentation hooks in dispatch /
engine / fleet."""

import glob
import json
import math
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from repro.obs.export import (
    ObsServer,
    prometheus_text,
    read_snapshot_file,
    write_snapshot,
)
from repro.obs.metrics import (
    BUCKET_BOUNDS,
    Histogram,
    MetricsRegistry,
    get_registry,
    histogram_quantile,
    merge_snapshots,
    set_registry,
    summarize_histograms,
)
from repro.obs import trace as obs_trace
from repro.obs.trace import (
    SpanRing,
    Tracer,
    configure_tracer,
    dump_recorded,
    export_chrome_trace,
    get_tracer,
    install_jax_hooks,
    recorded_spans,
    set_span_ring,
    span,
    validate_trace,
)


@pytest.fixture
def fresh_registry():
    """Swap in an isolated default registry; restore the old one after."""
    old = get_registry()
    reg = set_registry(MetricsRegistry())
    try:
        yield reg
    finally:
        set_registry(old)


@pytest.fixture
def no_tracer():
    """Force the NULL tracer for the test, restoring state after."""
    configure_tracer(None)
    yield
    configure_tracer(None)


@pytest.fixture
def ring():
    """An empty span ring for the test; the process ring is restored after."""
    fresh = SpanRing()
    old = set_span_ring(fresh)
    try:
        yield fresh
    finally:
        set_span_ring(old)


# ---------------------------------------------------------------------------
# metrics core
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_roundtrip():
    reg = MetricsRegistry()
    reg.add("requests_total", kernel="syr2k", path="fast_hit")
    reg.add("requests_total", 2.0, kernel="syr2k", path="fast_hit")
    reg.set_gauge("pending", 3, host="a")
    reg.set_gauge("pending", 7, host="a")   # last write wins
    reg.observe("latency_seconds", 0.001, kernel="syr2k")
    reg.observe("latency_seconds", 0.002, kernel="syr2k")
    snap = reg.snapshot()
    assert snap["schema"] == "repro.obs/1"
    assert snap["buckets"] == list(BUCKET_BOUNDS)
    (c,) = snap["counters"]
    assert c == {"name": "requests_total",
                 "labels": {"kernel": "syr2k", "path": "fast_hit"},
                 "value": 3.0}
    (g,) = snap["gauges"]
    assert g["value"] == 7.0
    (h,) = snap["histograms"]
    assert h["count"] == 2 and abs(h["sum"] - 0.003) < 1e-12
    assert sum(h["counts"]) == 2
    # snapshot round-trips through json unchanged
    assert json.loads(json.dumps(snap)) == snap


def test_concurrent_recording_folds_to_exact_totals():
    """>= 4 threads hammer one registry; after they quiesce, the folded
    snapshot must account for every single operation."""
    reg = MetricsRegistry()
    n_threads, n_ops = 6, 5000
    barrier = threading.Barrier(n_threads)

    def worker(tid):
        barrier.wait()
        for i in range(n_ops):
            reg.add("ops_total", thread="shared")
            reg.observe("lat_seconds", (i % 100 + 1) * 1e-6, thread="shared")

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = reg.snapshot()
    (c,) = snap["counters"]
    assert c["value"] == float(n_threads * n_ops)
    (h,) = snap["histograms"]
    assert h["count"] == n_threads * n_ops
    assert sum(h["counts"]) == n_threads * n_ops
    expected_sum = n_threads * sum((i % 100 + 1) * 1e-6 for i in range(n_ops))
    assert abs(h["sum"] - expected_sum) < 1e-9


def test_histogram_merge_associative_commutative():
    """Any grouping and any order of merges yields the identical snapshot —
    the property that makes cross-host folding deterministic. Seeded-rng
    shuffle property test (same idiom as the fleet merge tests)."""
    rng = np.random.default_rng(42)

    def random_snapshot(seed):
        reg = MetricsRegistry()
        r = np.random.default_rng(seed)
        for _ in range(50):
            reg.add("c_total", float(r.integers(1, 5)),
                    k=str(r.integers(0, 3)))
            reg.observe("h_seconds", float(r.uniform(1e-6, 10.0)),
                        k=str(r.integers(0, 3)))
        return reg.snapshot()

    def assert_equivalent(a, b):
        # bucket counts (the quantile inputs) must be BIT-identical in any
        # merge order; float sums are only reassociated, so equal to ulps
        assert [(h["name"], h["labels"], h["counts"], h["count"])
                for h in a["histograms"]] \
            == [(h["name"], h["labels"], h["counts"], h["count"])
                for h in b["histograms"]]
        for ha, hb in zip(a["histograms"], b["histograms"]):
            assert math.isclose(ha["sum"], hb["sum"], rel_tol=1e-12)
        assert [(c["name"], c["labels"]) for c in a["counters"]] \
            == [(c["name"], c["labels"]) for c in b["counters"]]
        for ca, cb in zip(a["counters"], b["counters"]):
            assert math.isclose(ca["value"], cb["value"], rel_tol=1e-12)

    snaps = [random_snapshot(s) for s in range(6)]
    reference = merge_snapshots(*snaps)
    for _ in range(10):
        order = list(range(len(snaps)))
        rng.shuffle(order)
        shuffled = [snaps[i] for i in order]
        # commutative: any permutation merges to the same result
        assert_equivalent(merge_snapshots(*shuffled), reference)
        # associative: ((a+b)+c)+... == a+(b+(c+...)) — fold pairwise left
        # and right and compare
        left = shuffled[0]
        for s in shuffled[1:]:
            left = merge_snapshots(left, s)
        right = shuffled[-1]
        for s in reversed(shuffled[:-1]):
            right = merge_snapshots(s, right)
        assert_equivalent(left, right)
        assert_equivalent(left, reference)


def test_merge_rejects_bucket_schema_mismatch():
    reg = MetricsRegistry()
    reg.observe("h_seconds", 0.5)
    snap = reg.snapshot()
    alien = dict(snap, buckets=[0.1, 1.0, 10.0])
    with pytest.raises(ValueError, match="bucket schema"):
        merge_snapshots(snap, alien)


def test_histogram_quantiles():
    h = Histogram()
    for _ in range(100):
        h.observe(0.001)  # ~1ms
    assert 0.0005 < h.quantile(0.5) < 0.002
    assert 0.0005 < h.quantile(0.99) < 0.002
    # +Inf bucket clamps to the largest finite bound
    h2 = Histogram()
    h2.observe(1e9)
    assert h2.quantile(0.5) == BUCKET_BOUNDS[-1]
    # empty histogram -> NaN
    assert math.isnan(histogram_quantile([0] * (len(BUCKET_BOUNDS) + 1), 0.5))


def test_summarize_histograms_filters():
    reg = MetricsRegistry()
    reg.observe("dispatch_execute_seconds", 0.01, kernel="syr2k")
    reg.observe("fleet_pull_seconds", 0.02, host="a")
    snap = reg.snapshot()
    rows = summarize_histograms(snap, name="dispatch_execute_seconds")
    assert len(rows) == 1 and rows[0]["count"] == 1
    assert rows[0]["p50"] <= rows[0]["p99"]
    rows = summarize_histograms(snap, prefix="fleet_")
    assert [r["name"] for r in rows] == ["fleet_pull_seconds"]


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------


def test_trace_roundtrip_and_torn_tail(tmp_path, no_tracer):
    path = str(tmp_path / "trace.jsonl")
    tracer = Tracer(path, process_name="test-proc")
    with tracer.span("work.outer", kernel="syr2k"):
        with tracer.span("work.inner"):
            pass
    tracer.instant("marker", n=3)
    tracer.close()
    report = validate_trace(path)
    assert report["ok"]
    assert report["invalid"] == 0 and report["skipped"] == 0
    assert {"work.outer", "work.inner", "marker"} <= set(report["names"])
    # every X span carries microsecond ts + dur and pid/tid
    events = [json.loads(line) for line in open(path)]
    spans = [e for e in events if e["ph"] == "X"]
    assert len(spans) == 2
    for ev in spans:
        assert ev["dur"] >= 0 and ev["ts"] > 0 and ev["pid"] == os.getpid()
    # inner nested within outer on the timeline
    inner = next(e for e in spans if e["name"] == "work.inner")
    outer = next(e for e in spans if e["name"] == "work.outer")
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]

    # a torn tail (killed writer) is skipped, not fatal — and a new Tracer
    # appending afterwards repairs it so its events stay line-delimited
    with open(path, "a") as f:
        f.write('{"name": "torn", "ph": "X", "ts": 1')
    report = validate_trace(path)
    assert report["ok"] and report["skipped"] == 1
    tracer2 = Tracer(path)
    with tracer2.span("after.crash"):
        pass
    tracer2.close()
    report = validate_trace(path)
    assert report["ok"] and "after.crash" in report["names"]


def test_trace_error_span_and_missing_file(tmp_path, no_tracer):
    path = str(tmp_path / "t.jsonl")
    tracer = Tracer(path)
    with pytest.raises(RuntimeError):
        with tracer.span("boom"):
            raise RuntimeError("x")
    tracer.close()
    (ev,) = [json.loads(line) for line in open(path)]
    assert ev["args"]["error"] == "RuntimeError"
    assert not validate_trace(str(tmp_path / "absent.jsonl"))["ok"]


def test_export_chrome_trace_is_loadable_json(tmp_path, no_tracer):
    src = str(tmp_path / "trace.jsonl")
    out = str(tmp_path / "trace.chrome.json")
    tracer = Tracer(src)
    with tracer.span("a"):
        pass
    tracer.close()
    n = export_chrome_trace(src, out)
    assert n == 1
    doc = json.load(open(out))
    assert doc["traceEvents"][0]["name"] == "a"


def test_env_var_activates_tracer(tmp_path, no_tracer, monkeypatch):
    path = str(tmp_path / "env.jsonl")
    monkeypatch.setenv("REPRO_TRACE", path)
    # reset the lazy singleton so the env var is consulted
    import repro.obs.trace as trace_mod
    trace_mod._tracer = None
    t = get_tracer()
    assert t.enabled and t.path == path
    with t.span("via.env"):
        pass
    configure_tracer(None)
    assert "via.env" in validate_trace(path)["names"]


# ---------------------------------------------------------------------------
# the span ring: parent ids, bound, profiler clock, compile events
# ---------------------------------------------------------------------------


def test_span_parents_follow_each_threads_own_stack(ring, no_tracer):
    """A span's parent is the innermost span open on its own thread: two
    threads nesting at the same time never adopt each other's spans."""
    both_open = threading.Barrier(2, timeout=10)
    ids = {}

    def work(tag):
        with span(f"{tag}.outer") as outer:
            both_open.wait()
            with span(f"{tag}.inner") as inner:
                both_open.wait()
            ids[tag] = (outer.id, inner.id)

    threads = [threading.Thread(target=work, args=(t,)) for t in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert not any(t.is_alive() for t in threads)
    by_name = {s.name: s for s in recorded_spans()}
    for tag in ("a", "b"):
        outer, inner = by_name[f"{tag}.outer"], by_name[f"{tag}.inner"]
        assert (outer.id, inner.id) == ids[tag]
        assert outer.parent == 0 and inner.parent == outer.id
        assert outer.tid == inner.tid
        assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    assert by_name["a.outer"].tid != by_name["b.outer"].tid
    # closed spans leave the stack: the next span on this thread is a root
    with span("after"):
        pass
    assert recorded_spans()[-1].parent == 0


def test_ring_keeps_the_newest_and_counts_what_it_drops(fresh_registry,
                                                         no_tracer, tmp_path):
    small = SpanRing(maxlen=4)
    old = set_span_ring(small)
    try:
        for i in range(10):
            with span("s", i=i):
                pass
        got = recorded_spans()
    finally:
        set_span_ring(old)
    assert [s.attrs["i"] for s in got] == [6, 7, 8, 9]
    assert small.dropped == 6
    (c,) = [c for c in fresh_registry.snapshot()["counters"]
            if c["name"] == "obs_spans_dropped_total"]
    assert c["value"] == 6.0


def test_dump_recorded_writes_loadable_chrome_jsonl(ring, no_tracer, tmp_path):
    with span("outer", slot=3):
        with span("inner"):
            pass
    path = str(tmp_path / "ring.jsonl")
    assert dump_recorded(path) == 2
    report = validate_trace(path)
    assert report["ok"] and report["names"] == ["inner", "outer"]
    events = {e["name"]: e for e in map(json.loads, open(path))}
    assert events["inner"]["args"]["parent_id"] == events["outer"]["args"]["span_id"]
    assert events["outer"]["args"]["slot"] == 3
    assert export_chrome_trace(path, str(tmp_path / "ring.json")) == 2


def test_fresh_jit_inside_a_span_records_compile_children(ring, fresh_registry,
                                                          no_tracer):
    """JAX's trace, lowering and backend-compile events of a fresh jit land
    as child spans of the span open when they happened, and in the
    ``jax_compile_*`` counters under that span's name."""
    import jax
    import jax.numpy as jnp

    install_jax_hooks()
    with span("test.compiling") as owner:
        jax.jit(lambda x: x * 7.0 - 1.0)(jnp.arange(3.0)).block_until_ready()
    spans = recorded_spans()
    (own,) = [s for s in spans if s.name == "test.compiling"]
    kids = [s for s in spans if s.parent == owner.id]
    assert {"jax.trace", "jax.lower", "jax.compile"} <= {s.name for s in kids}
    for s in kids:
        assert s.attrs["seconds"] >= 0
        # each event ends where it is reported, inside its owner (its start
        # comes from JAX's own duration, on another clock: allow 1 ms)
        assert own.start_ns - 1_000_000 <= s.start_ns <= s.end_ns <= own.end_ns
    counters = {(c["name"], c["labels"]["event"], c["labels"]["span"]): c["value"]
                for c in fresh_registry.snapshot()["counters"]
                if c["name"].startswith("jax_compile_")}
    for event in ("jax.trace", "jax.lower", "jax.compile"):
        assert counters[("jax_compile_events_total", event, "test.compiling")] >= 1
        assert counters[("jax_compile_seconds", event, "test.compiling")] >= 0


def test_spans_sit_on_the_profiler_clock(ring, no_tracer, tmp_path):
    """Under a profiler session each span is also a host annotation in the
    xplane; its offset from an enclosing anchor annotation agrees with the
    recorded ``perf_counter_ns`` offset within 100 us."""
    import jax
    from jax.profiler import ProfileData

    install_jax_hooks()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("test.anchor"):
            anchor_ns = time.perf_counter_ns()
            for i in range(4):
                with span("test.outer", i=i):
                    time.sleep(0.002)
                    with span("test.inner"):
                        time.sleep(0.001)
    (pb,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {}
    for plane in ProfileData.from_file(pb).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    host.setdefault(e.name, []).append(e.start_ns)
    (xp_anchor,) = host["test.anchor"]
    for name in ("test.outer", "test.inner"):
        xp = sorted(t - xp_anchor for t in host[name])
        rec = sorted(s.start_ns - anchor_ns for s in recorded_spans() if s.name == name)
        assert len(xp) == len(rec) == 4
        assert max(abs(a - b) for a, b in zip(xp, rec)) < 100_000


def test_no_annotation_is_entered_without_a_profiler_session(ring, no_tracer,
                                                             monkeypatch, tmp_path):
    import jax

    install_jax_hooks()
    entered = []
    real = obs_trace._annotation

    def counting(name):
        entered.append(name)
        return real(name)

    monkeypatch.setattr(obs_trace, "_annotation", counting)
    for _ in range(3):
        with span("quiet"):
            pass
    assert entered == []
    assert [s.name for s in recorded_spans()] == ["quiet"] * 3
    with jax.profiler.trace(str(tmp_path)):
        with span("loud"):
            pass
    assert entered == ["loud"]


# ---------------------------------------------------------------------------
# export: snapshots, Prometheus text, HTTP scrape
# ---------------------------------------------------------------------------


def test_snapshot_file_write_read_merge(tmp_path):
    path = str(tmp_path / "obs.jsonl")
    r1, r2 = MetricsRegistry(), MetricsRegistry()
    r1.add("c_total", 2.0, host="a")
    r2.add("c_total", 3.0, host="a")
    r1.observe("h_seconds", 0.01)
    r2.observe("h_seconds", 0.02)
    write_snapshot(path, registry=r1, source="test")
    write_snapshot(path, registry=r2, source="test")
    lines = read_snapshot_file(path, merge=False)
    assert len(lines) == 2 and all(line["source"] == "test" for line in lines)
    merged = read_snapshot_file(path)
    (c,) = merged["counters"]
    assert c["value"] == 5.0
    (h,) = merged["histograms"]
    assert h["count"] == 2


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.add("requests_total", 4, kernel="syr2k", path="fast_hit")
    reg.set_gauge("pending", 2, host='we"ird')
    reg.observe("execute_seconds", 0.001, kernel="syr2k")
    text = prometheus_text(registry=reg)
    assert '# TYPE repro_requests_total counter' in text
    assert 'repro_requests_total{kernel="syr2k",path="fast_hit"} 4' in text
    assert '# TYPE repro_execute_seconds histogram' in text
    assert 'repro_execute_seconds_count{kernel="syr2k"} 1' in text
    assert 'le="+Inf"' in text
    assert '\\"' in text  # label values escaped
    # _bucket series are cumulative and end at the total count
    bucket_lines = [ln for ln in text.splitlines()
                    if ln.startswith("repro_execute_seconds_bucket")]
    assert len(bucket_lines) == len(BUCKET_BOUNDS) + 1
    assert bucket_lines[-1].endswith(" 1")
    cums = [int(ln.rsplit(" ", 1)[1]) for ln in bucket_lines]
    assert cums == sorted(cums)


def test_obs_server_scrape():
    reg = MetricsRegistry()
    reg.observe("execute_seconds", 0.005, kernel="syr2k")
    server = ObsServer(registry=reg).start()
    try:
        with urllib.request.urlopen(server.url + "/metrics") as r:
            text = r.read().decode()
        assert "repro_execute_seconds_count" in text
        with urllib.request.urlopen(server.url + "/snapshot") as r:
            snap = json.loads(r.read())
        assert snap["schema"] == "repro.obs/1"
        assert urllib.request.urlopen(server.url + "/nope").status == 404
    except urllib.error.HTTPError as e:
        assert e.code == 404  # the /nope probe
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# integration: engine + fleet instrumentation
# ---------------------------------------------------------------------------


def test_campaign_records_metrics_and_spans(tmp_path, fresh_registry, no_tracer):
    from repro.core import EvalResult
    from repro.core.space import ConfigurationSpace, Ordinal
    from repro.engine import Campaign

    trace_path = str(tmp_path / "campaign.jsonl")
    configure_tracer(trace_path)
    cs = ConfigurationSpace(seed=1)
    cs.add_hyperparameter(Ordinal("s", (1, 2, 4, 8), default=1))
    res = Campaign(cs, lambda cfg: EvalResult(1.0 / cfg["s"], True, {}),
                   max_evals=4, n_initial=2, seed=1).run()
    configure_tracer(None)
    assert res.best is not None
    # timing dicts unchanged for existing consumers...
    assert res.timings["n_tells"] == 4 and res.timings["ask_sec"] >= 0.0
    # ...and the same phases landed in the registry as histograms
    rows = summarize_histograms(fresh_registry.snapshot(), prefix="campaign_")
    by_name = {r["name"]: r for r in rows}
    assert by_name["campaign_tell_seconds"]["count"] == 4
    assert by_name["campaign_evaluate_seconds"]["count"] == 4
    assert by_name["campaign_ask_seconds"]["count"] == res.timings["n_asks"]
    # ...and the trace timeline has every phase (+ the db-less campaign has
    # no checkpoint spans)
    report = validate_trace(trace_path)
    assert report["ok"]
    assert {"campaign.ask", "campaign.evaluate", "campaign.tell"} \
        <= set(report["names"])


def test_sync_agent_records_cycle_durations(tmp_path, fresh_registry):
    from repro.dispatch.store import TuningStore
    from repro.fleet import Replica, SyncAgent, transport_from_spec

    replica = Replica(TuningStore(str(tmp_path / "store")))
    transport = transport_from_spec("file:" + str(tmp_path / "shared"))
    agent = SyncAgent(replica, transport)
    out = agent.sync_once()
    agent.sync_once()
    # the return dict keeps its pre-obs shape (quiesce loops compare exactly)
    assert out == {"applied": 0, "published": 0, "pending": 0}
    assert agent.stats["cycles"] == 2
    for k in ("pull_sec", "merge_sec", "push_sec"):
        assert agent.stats[k] >= 0.0
    rows = {r["name"]: r for r in summarize_histograms(
        fresh_registry.snapshot(), prefix="fleet_")}
    for name in ("fleet_pull_seconds", "fleet_merge_seconds",
                 "fleet_push_seconds", "fleet_cycle_seconds"):
        assert rows[name]["count"] == 2, name
    # lag is only observable from the second cycle on (needs a prior sync)
    assert rows["fleet_replication_lag_seconds"]["count"] == 1
    # and the replica's status surfaces the same summaries
    status = replica.status(transport)
    assert {r["name"] for r in status["obs"]} == set(rows)


def test_fleet_server_metrics_route(tmp_path, fresh_registry):
    from repro.dispatch.store import TuningStore
    from repro.fleet import FleetServer, Replica
    from repro.fleet.http import HttpTransport

    fresh_registry.observe("fleet_pull_seconds", 0.01, host="me")
    replica = Replica(TuningStore(str(tmp_path / "store")))
    server = FleetServer(replica).start()
    try:
        with urllib.request.urlopen(server.url + "/metrics") as r:
            text = r.read().decode()
        assert "repro_fleet_pull_seconds_count" in text
        peer = HttpTransport(server.url).status()
        assert peer["host"] == replica.host_id and "obs" in peer
    finally:
        server.stop()
