"""Continuous-batching serve benchmark — the ground truth for every
"serving got faster" claim.

Replays a mixed prompt/output-length workload with Poisson arrivals through
the real serving stack (``serve.prefill`` + ``decode_step`` on a
:class:`repro.serve.PagedKVCache`), reshaping the decode batch as requests
join and leave, and reports p50/p99 inter-token latency, TTFT, and token
throughput per serving mode:

  * ``einsum``  — the pre-paging reference: one dense max-batch/max-len
    cache, every step attends over the full allocation (the stub-grade
    cache this PR replaces);
  * ``default`` — paged cache + dispatch-service *default* decode config
    (empty tuning store);
  * ``tuned``   — paged cache + a store seeded by a short timing campaign
    over the decode space at the serving signature: the kernel's
    ``impl``/``bk``/``hg`` axes and the cache's ``page`` layout axis are
    tuned together (page decides the seq-bucket ladder every view is cut
    on, the compute-vs-retrace trade).

Writes ``BENCH_serve.json`` via ``benchmarks.common.write_bench_json`` and
``BENCH_serve.obs.jsonl`` — an ``repro.obs`` metrics snapshot from the tuned
run's service registry, with ``dispatch_execute_seconds`` histograms for
both the prefill (flash_attention) and decode (decode_attention) kernels,
so ``repro-obs summarize --metrics`` shows the two hot paths side by side.

The run fails (exit 1) when any mode's p99 token latency is missing,
non-finite, or degenerate — the CI serve-smoke tripwire.

Usage::

    PYTHONPATH=src python benchmarks/serve_bench.py            # full run
    PYTHONPATH=src python benchmarks/serve_bench.py --quick    # CI smoke
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.common import time_callable, write_bench_json  # noqa: E402
from repro.analyze.feasibility import check_config  # noqa: E402
from repro.configs import get_reduced  # noqa: E402
from repro.dispatch import DispatchService, TuningRecord, TuningStore  # noqa: E402
from repro.kernels.model_kernels import (  # noqa: E402
    decode_attention_builder,
    decode_attention_signature,
    init_decode_attention,
    init_flash_attention,
)
from repro.kernels.spaces import kernel_space  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.obs.export import write_snapshot  # noqa: E402
from repro.obs.metrics import MetricsRegistry  # noqa: E402
from repro.serve import PagedKVCache, make_serve_step, prefill  # noqa: E402


# ---------------------------------------------------------------------------
# workload
# ---------------------------------------------------------------------------


def make_workload(n_requests: int, rate: float, prompt_lens, out_mean: int,
                  out_cap: int, seed: int):
    """Deterministic request list: Poisson arrivals (exponential gaps at
    ``rate`` req/s), prompt lengths cycled from a fixed set, output lengths
    4 + geometric(mean ``out_mean``) capped at ``out_cap``."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate, size=n_requests)
    arrivals = np.cumsum(gaps)
    reqs = []
    for i in range(n_requests):
        out = 4 + int(rng.geometric(1.0 / max(out_mean - 4, 1)))
        reqs.append({
            "id": i,
            "arrival": float(arrivals[i]),
            "prompt_len": int(prompt_lens[i % len(prompt_lens)]),
            "out_len": int(min(out, out_cap)),
        })
    return reqs


def _pad_batch(active, free, max_batch):
    """Round the batch up the {1,2,4,8,...} ladder with free slots so the
    serve step sees a bounded set of batch shapes (padding rows decode
    garbage at position 0 that admission later overwrites)."""
    b = 1
    while b < len(active):
        b *= 2
    b = min(b, max_batch)
    pad = [s for s in free if s not in active][: b - len(active)]
    return active + pad


# ---------------------------------------------------------------------------
# one serving run
# ---------------------------------------------------------------------------


def run_mode(mode: str, cfg, params, workload, *, max_batch: int, max_len: int,
             page_size: int, service, round_cap: int = 8) -> dict:
    """Serve ``workload`` to completion; returns latency/throughput metrics.

    ``einsum`` mode decodes the full dense allocation every step (no views);
    paged modes cut bucketed views per round and write back on membership or
    bucket changes."""
    paged = mode != "einsum"
    pc = PagedKVCache(cfg, max_batch, max_len,
                      page_size=page_size if paged else max_len)
    serve = make_serve_step(cfg, service=service) if service is not None \
        else jax.jit(make_serve_step(cfg))
    pending = sorted(workload, key=lambda r: r["arrival"])
    pending = list(pending)
    state: dict[int, dict] = {}   # slot -> {req, tok, done}
    token_lat: list[float] = []
    ttft: list[float] = []
    tokens_out = 0
    peak = pc.stats()   # paged accounting at peak residency, not at drain

    t0 = time.perf_counter()
    skipped = 0.0   # idle fast-forward: virtual seconds skipped while empty

    def clock():
        return time.perf_counter() - t0 + skipped

    while pending or state:
        # admissions: arrivals due now, while slots are free
        free = pc.free_slots()
        while pending and free and pending[0]["arrival"] <= clock():
            req = pending.pop(0)
            slot = free.pop(0)
            prompt = jax.random.randint(
                jax.random.PRNGKey(1000 + req["id"]),
                (1, req["prompt_len"]), 0, cfg.vocab_size)
            logits, cache = prefill(params, {"tokens": prompt}, cfg,
                                    max_len=pc.alloc, service=service)
            pc.admit(slot, cache, req["prompt_len"])
            first = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)
            jax.block_until_ready(first)
            state[slot] = {"req": req, "tok": int(first[0]), "made": 1}
            tokens_out += 1
            ttft.append(clock() - req["arrival"])
            if state[slot]["made"] >= req["out_len"]:
                pc.release(slot)
                del state[slot]
        if not state:
            if pending:   # idle: fast-forward to the next arrival
                skipped += max(0.0, pending[0]["arrival"] - clock()) + 1e-9
            continue

        # one decode round: fixed membership, fixed bucket
        active = sorted(state)
        cur_stats = pc.stats()
        if cur_stats["tokens_resident"] > peak["tokens_resident"]:
            peak = cur_stats
        if paged:
            slots = _pad_batch(active, pc.free_slots(), max_batch)
            steps = min(round_cap,
                        min(state[s]["req"]["out_len"] - state[s]["made"]
                            for s in active))
            bucket = pc.seq_bucket(slots, extra=steps)
            view = pc.view(slots, bucket)
        else:
            slots = list(range(max_batch))
            steps = min(round_cap,
                        min(state[s]["req"]["out_len"] - state[s]["made"]
                            for s in active))
            bucket = pc.alloc
            view = pc.buf
        for _ in range(steps):
            cur = jnp.asarray([[state[s]["tok"] if s in state else 0]
                               for s in slots], jnp.int32)
            pos = jnp.asarray([int(pc.pos[s]) + 1 if s in state else 0
                               for s in slots], jnp.int32)
            ts = time.perf_counter()
            nxt, _, view = serve(params, view, cur, pos)
            jax.block_until_ready(nxt)
            dt = time.perf_counter() - ts
            pc.advance(active)
            tokens_out += len(active)
            token_lat.extend([dt] * len(active))
            for i, s in enumerate(slots):
                if s in state:
                    state[s]["tok"] = int(nxt[i, 0])
                    state[s]["made"] += 1
        if paged:
            pc.writeback(slots, bucket, view)
        else:
            pc.buf = view
        for s in list(active):
            if state[s]["made"] >= state[s]["req"]["out_len"]:
                pc.release(s)
                del state[s]

    wall = time.perf_counter() - t0
    lat = np.asarray(token_lat)
    out = {
        "mode": mode,
        "page_size": page_size if paged else None,
        "requests": len(workload),
        "tokens": tokens_out,
        "wall_sec": wall,
        "throughput_tok_s": tokens_out / wall if wall > 0 else None,
        "token_lat_p50_ms": float(np.percentile(lat, 50) * 1e3) if lat.size else None,
        "token_lat_p99_ms": float(np.percentile(lat, 99) * 1e3) if lat.size else None,
        "ttft_p50_ms": float(np.percentile(ttft, 50) * 1e3) if ttft else None,
    }
    out["kv_cache"] = peak
    if service is not None:
        service.attach_kv_cache(pc)
        tel = service.telemetry()
        out["dispatch"] = {k: tel[k] for k in
                           ("store_exact", "store_near", "store_default",
                            "exec_hit", "exec_miss", "build_failed",
                            "infeasible")}
    return out


# ---------------------------------------------------------------------------
# the inline decode-space campaign (mode "tuned")
# ---------------------------------------------------------------------------


def tune_decode(cfg, *, max_batch: int, resident: int, n_candidates: int,
                seed: int) -> tuple[dict, list]:
    """Short timing campaign over the decode space at the serving signature.
    Each candidate is wall-clocked at *its own* seq bucket —
    ``ceil(resident/page)*page`` — so the ``page`` layout axis's padded
    attention work is part of the measured objective, exactly the
    layout-belongs-in-the-space point the bench exists to demonstrate."""
    K, G, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.hd
    BH = max_batch * K
    cs = kernel_space("decode_attention", target="host", seed=seed)
    cands = [dict(cs.default_configuration())]
    while len(cands) < n_candidates:
        c = dict(cs.sample_configuration())
        if c not in cands:
            cands.append(c)
    trace, best, best_t = [], None, float("inf")
    for c in cands:
        page = int(c["page"])
        s_eff = -(-resident // page) * page   # the bucket this page serves
        if not check_config("decode_attention", c,
                            dims=(BH, G, s_eff, hd), target="host").ok:
            continue
        args = init_decode_attention(BH, G, s_eff, hd)
        t = time_callable(decode_attention_builder(c), args,
                          repeats=3, warmup=1)
        trace.append({"config": c, "seconds": t})
        if t < best_t:
            best, best_t = c, t
    return best, trace


def seed_store(store, cfg, best: dict, *, max_batch: int, max_resident: int,
               alloc: int) -> int:
    """Publish the tuned config for every signature the serving loop will
    derive: batch ladder x page-aligned seq buckets (plus the prefill
    replay's full-allocation bucket)."""
    K, G, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.hd
    page = int(best["page"])
    buckets = set(range(page, -(-max_resident // page) * page + 1, page))
    buckets.add(-(-alloc // page) * page)
    batches = {1}
    b = 1
    while b < max_batch:
        b = min(b * 2, max_batch)
        batches.add(b)
    n = 0
    for bsz in sorted(batches):
        for s in sorted(buckets):
            sig = decode_attention_signature(bsz * K, G, s, hd)
            if store.put(TuningRecord("decode_attention", sig, "host",
                                      dict(best), 1.0)):
                n += 1
    return n


# ---------------------------------------------------------------------------
# obs probe: real execute-latency samples for prefill + decode kernels
# ---------------------------------------------------------------------------


def probe_kernels(service, cfg, *, max_batch: int, bucket: int,
                  prompt_len: int, reps: int = 20) -> None:
    """Eager dispatch calls at the serving shapes so the obs snapshot's
    ``dispatch_execute_seconds`` histograms carry real per-call samples for
    both hot paths (in-model dispatches run under jit and record nothing)."""
    K, G, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.hd
    BH = max_batch * K
    args = init_decode_attention(BH, G, bucket, hd)
    fn = service.dispatch("decode_attention", *args, ring=False, window=0)
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    fargs = init_flash_attention(BH, prompt_len, prompt_len, hd)
    fn = service.dispatch("flash_attention", *fargs, causal=True)
    for _ in range(reps):
        jax.block_until_ready(fn(*fargs))


# ---------------------------------------------------------------------------
# guard phase (--inject-drift): shadow overhead + the drift-heal loop
# ---------------------------------------------------------------------------


def guard_drift_phase(cfg, best: dict, *, store_root: str, max_batch: int,
                      bucket: int, quick: bool) -> dict:
    """Measure the guard's serving cost and prove the drift loop on the
    decode hot path.

    Shadow overhead is measured where it is actually paid: eager dispatch
    calls at the serving shape (in-model dispatches are jitted, so shadow
    sampling — like all per-call instrumentation — only sees the eager
    path). With ``epsilon=0.1`` nine of ten calls pay one counter check,
    so the *median* call is a non-shadow call and must stay within 2% of
    an unguarded service — the shadow cost lands in the tail by design.
    Guarded and unguarded calls are interleaved and the overhead gate uses
    min-of-N: on a shared box, scheduler noise dwarfs a ~1us deterministic
    cost at the median, and the minimum isolates exactly the per-call cost
    the 2% claim is about (p50s of both are still reported). Then
    ``dispatch.latency`` is injected and the watcher must quarantine the
    served record and degrade to the default config within two windows."""
    from repro.guard import (GuardAgent, ShadowPolicy, WatchPolicy,
                             guard_counters, inject)

    K, G, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.hd
    BH = max_batch * K
    args = init_decode_attention(BH, G, bucket, hd)
    sig = decode_attention_signature(BH, G, bucket, hd)
    reps = 100 if quick else 400
    epsilon = 0.1

    def serve(svc):
        fn = svc.dispatch("decode_attention", *args, ring=False, window=0)
        jax.block_until_ready(fn(*args))    # compile outside the timing
        return fn

    # -- unguarded reference ------------------------------------------------
    store_p = TuningStore(os.path.join(store_root, "guard_plain"))
    store_p.put(TuningRecord("decode_attention", sig, "host", dict(best), 1.0))
    fn_plain = serve(DispatchService(store_p, metrics=MetricsRegistry()))

    # -- guarded service: shadow epsilon + drift watch ----------------------
    store_g = TuningStore(os.path.join(store_root, "guard"))
    store_g.put(TuningRecord("decode_attention", sig, "host", dict(best), 1.0))
    svc = DispatchService(store_g, metrics=MetricsRegistry())
    guard = GuardAgent(
        svc,
        watch=WatchPolicy(drift_factor=3.0, hysteresis=2, cooldown_sec=0.0,
                          min_samples=8),
        shadow=ShadowPolicy(epsilon=epsilon, challenger_fraction=0.0))
    svc.attach_guard(guard)
    fn = serve(svc)

    t_plain, t_shadow = [], []
    for _ in range(reps):           # interleaved: box noise hits both alike
        t0 = time.perf_counter()
        jax.block_until_ready(fn_plain(*args))
        t_plain.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))    # shadow tells sharpen the seed
        t_shadow.append(time.perf_counter() - t0)
    p50_plain = float(np.percentile(t_plain, 50))
    p50_shadow = float(np.percentile(t_shadow, 50))
    overhead = min(t_shadow) / min(t_plain) - 1.0

    # -- injected latency regression: the watcher must heal it --------------
    guard.check_once()                       # window base
    delay = max(0.05, 10.0 * p50_plain)      # unambiguous drift
    with inject("dispatch.latency", delay_sec=delay,
                where={"kernel": "decode_attention"}):
        for _ in range(12):
            fn(*args)
        first = guard.check_once()           # breach 1 of 2: hysteresis
        for _ in range(12):
            fn(*args)
        decisions = guard.check_once()       # breach 2: quarantine
    drift_ok = (first == [] and len(decisions) == 1
                and decisions[0]["reason"].startswith("drift:"))
    # degraded serving: the quarantined record must not resolve again
    before = svc.stats["store_default"]
    serve(svc)
    fallback_ok = svc.stats["store_default"] == before + 1

    return {
        "epsilon": epsilon,
        "p50_plain_ms": p50_plain * 1e3,
        "p50_shadow_ms": p50_shadow * 1e3,
        "shadow_overhead_frac": overhead,
        "drift_ok": drift_ok,
        "fallback_ok": fallback_ok,
        "decisions": decisions,
        "shadow": guard.shadow.snapshot_stats(),
        "quarantines": guard.stats["quarantines"],
        "fallbacks": guard.stats["fallbacks"],
        "counters": guard_counters(svc.metrics.snapshot()),
    }


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke sizes (seconds, not minutes)")
    ap.add_argument("--inject-drift", action="store_true",
                    help="also run the guard phase: shadow-eval overhead "
                         "and an injected-latency drift-heal scenario")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=None)
    ap.add_argument("--rate", type=float, default=None,
                    help="Poisson arrival rate (req/s)")
    ap.add_argument("--candidates", type=int, default=None,
                    help="decode-space candidates for the tuned mode")
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--store", default="results/serve_bench_store")
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--obs-out", default="BENCH_serve.obs.jsonl")
    args = ap.parse_args(argv)

    quick = args.quick
    n_req = args.requests or (8 if quick else 24)
    max_len = args.max_len or (256 if quick else 1024)
    rate = args.rate or (50.0 if quick else 12.0)
    n_cand = args.candidates or (6 if quick else 12)
    prompt_lens = (8, 16) if quick else (16, 32, 48)
    out_mean, out_cap = (8, 12) if quick else (24, 48)

    cfg = dataclasses.replace(get_reduced("qwen2-0.5b"), dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    workload = make_workload(n_req, rate, prompt_lens, out_mean, out_cap,
                             args.seed)
    max_resident = max(r["prompt_len"] + r["out_len"] for r in workload)
    resident_typ = int(np.median(
        [r["prompt_len"] + r["out_len"] // 2 for r in workload]))

    print(f"# serve_bench: {n_req} requests, max_batch={args.max_batch}, "
          f"max_len={max_len}, rate={rate}/s, max_resident={max_resident}")

    results: dict[str, dict] = {}

    # -- einsum reference: dense full-allocation cache, no dispatch ----------
    results["einsum"] = run_mode(
        "einsum", cfg, params, workload, max_batch=args.max_batch,
        max_len=max_len, page_size=max_len, service=None)
    print(f"einsum : p50={results['einsum']['token_lat_p50_ms']:.3f}ms "
          f"p99={results['einsum']['token_lat_p99_ms']:.3f}ms "
          f"tput={results['einsum']['throughput_tok_s']:.1f} tok/s")

    # -- default: paged cache + empty store (space-default decode config) ----
    default_page = int(kernel_space("decode_attention",
                                    target="host").default_configuration()["page"])
    svc = DispatchService(TuningStore(os.path.join(args.store, "default")),
                          metrics=MetricsRegistry())
    results["default"] = run_mode(
        "default", cfg, params, workload, max_batch=args.max_batch,
        max_len=max_len, page_size=default_page, service=svc)
    print(f"default: p50={results['default']['token_lat_p50_ms']:.3f}ms "
          f"p99={results['default']['token_lat_p99_ms']:.3f}ms "
          f"tput={results['default']['throughput_tok_s']:.1f} tok/s "
          f"(page={default_page})")

    # -- tuned: inline campaign over impl/bk/hg/page, store-seeded -----------
    best, trace = tune_decode(cfg, max_batch=args.max_batch,
                              resident=resident_typ, n_candidates=n_cand,
                              seed=args.seed)
    store = TuningStore(os.path.join(args.store, "tuned"))
    n_rec = seed_store(store, cfg, best, max_batch=args.max_batch,
                       max_resident=max_resident, alloc=max_len)
    print(f"tuned config {best} ({n_rec} store records)")
    svc_t = DispatchService(store, metrics=MetricsRegistry())
    results["tuned"] = run_mode(
        "tuned", cfg, params, workload, max_batch=args.max_batch,
        max_len=max_len, page_size=int(best["page"]), service=svc_t)
    results["tuned"]["decode_config"] = best
    results["tuned"]["campaign"] = trace
    print(f"tuned  : p50={results['tuned']['token_lat_p50_ms']:.3f}ms "
          f"p99={results['tuned']['token_lat_p99_ms']:.3f}ms "
          f"tput={results['tuned']['throughput_tok_s']:.1f} tok/s "
          f"(page={best['page']})")

    # resolved-vs-default sanity: the tuned run must actually have served
    # store-resolved configs, not degraded to defaults
    disp = results["tuned"]["dispatch"]
    assert disp["store_exact"] >= 1, "tuned store records did not resolve"
    assert disp["build_failed"] == 0, "tuned config failed to build"

    # obs snapshot with real per-call samples for both hot-path kernels
    probe_kernels(svc_t, cfg, max_batch=args.max_batch,
                  bucket=min(-(-resident_typ // int(best["page"]))
                             * int(best["page"]), max_len),
                  prompt_len=max(prompt_lens))
    write_snapshot(args.obs_out, registry=svc_t.metrics, bench="serve",
                   mode="tuned")

    guard_payload = None
    if args.inject_drift:
        bucket = min(-(-resident_typ // int(best["page"])) * int(best["page"]),
                     max_len)
        print("# guard phase: shadow overhead + injected-drift heal loop")
        guard_payload = guard_drift_phase(
            cfg, best, store_root=args.store, max_batch=args.max_batch,
            bucket=bucket, quick=quick)
        print(f"guard  : shadow p50 {guard_payload['p50_shadow_ms']:.3f}ms vs "
              f"plain {guard_payload['p50_plain_ms']:.3f}ms "
              f"({guard_payload['shadow_overhead_frac']:+.2%}), "
              f"{guard_payload['shadow']['shadow_evals']} shadow evals, "
              f"{guard_payload['quarantines']} quarantine(s)")

    payload = {
        "workload": {
            "requests": n_req, "rate_req_s": rate,
            "prompt_lens": list(prompt_lens), "out_mean": out_mean,
            "out_cap": out_cap, "max_batch": args.max_batch,
            "max_len": max_len, "seed": args.seed,
            "arch": cfg.name, "reduced": True,
        },
        "modes": results,
        "speedup_p50_tuned_vs_einsum":
            results["einsum"]["token_lat_p50_ms"]
            / results["tuned"]["token_lat_p50_ms"],
        "speedup_p50_tuned_vs_default":
            results["default"]["token_lat_p50_ms"]
            / results["tuned"]["token_lat_p50_ms"],
    }
    if guard_payload is not None:
        payload["guard"] = guard_payload
    write_bench_json(args.out, payload)
    print(f"wrote {args.out} and {args.obs_out}")
    print(f"speedup p50 tuned vs einsum : "
          f"{payload['speedup_p50_tuned_vs_einsum']:.2f}x")
    print(f"speedup p50 tuned vs default: "
          f"{payload['speedup_p50_tuned_vs_default']:.2f}x")

    # guard tripwires: shadow epsilon must be ~free at the median, and the
    # injected regression must have been quarantined with fallback
    if guard_payload is not None:
        limit = 0.25 if quick else 0.02   # quick runs are too short to bound
        if guard_payload["shadow_overhead_frac"] > limit:
            print(f"FAIL: shadow epsilon costs "
                  f"{guard_payload['shadow_overhead_frac']:.1%} p50 "
                  f"(limit {limit:.0%})")
            return 1
        if not (guard_payload["drift_ok"] and guard_payload["fallback_ok"]):
            print(f"FAIL: drift-heal loop incomplete: {guard_payload}")
            return 1

    # tripwire: p99 must exist, be finite, and be non-degenerate
    for mode, r in results.items():
        p99 = r["token_lat_p99_ms"]
        if p99 is None or not np.isfinite(p99) or p99 <= 0.0:
            print(f"FAIL: degenerate p99 for mode {mode}: {p99}")
            return 1
        if r["token_lat_p50_ms"] > p99:
            print(f"FAIL: p50 > p99 for mode {mode}")
            return 1
    return 0


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
