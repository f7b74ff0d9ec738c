"""Serving path: prefill/greedy decode consistency and cache accounting."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_reduced
from repro.models import forward, init_params, loss_fn
from repro.obs.metrics import MetricsRegistry, get_registry, set_registry
from repro.serve import cache_bytes_per_token, greedy_decode, make_serve_step, prefill
from repro.serve.step import _replay

KEY = jax.random.PRNGKey(0)


def _cfg(arch):
    return dataclasses.replace(get_reduced(arch), dtype=jnp.float32)


def test_greedy_decode_runs_and_is_deterministic():
    cfg = _cfg("qwen2-0.5b")
    params = init_params(cfg, KEY)
    prompt = jax.random.randint(KEY, (2, 6), 0, cfg.vocab_size)
    out1 = greedy_decode(params, cfg, prompt, steps=5, max_len=16)
    out2 = greedy_decode(params, cfg, prompt, steps=5, max_len=16)
    assert out1.shape == (2, 5)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_prefill_cache_agrees_with_forward():
    cfg = _cfg("qwen1.5-0.5b")
    params = init_params(cfg, KEY)
    toks = jax.random.randint(jax.random.PRNGKey(4), (1, 8), 0, cfg.vocab_size)
    logits, cache = prefill(params, {"tokens": toks}, cfg, max_len=12)
    # next-step decode from the filled cache == forward on extended sequence
    serve = make_serve_step(cfg)
    nxt = jnp.argmax(logits[:, -1, :], -1).astype(toks.dtype)[:, None]
    _, step_logits, _ = serve(params, cache, nxt, 8)
    ext = jnp.concatenate([toks, nxt], axis=1)
    full_logits, _ = forward(params, {"tokens": ext}, cfg)
    np.testing.assert_allclose(np.asarray(step_logits),
                               np.asarray(full_logits[:, -1, :]),
                               atol=2e-2, rtol=2e-2)


def test_cache_bytes_accounting():
    # MLA's latent cache is dramatically smaller than GQA's at equal layers
    dsv2 = get_config("deepseek-v2-236b")
    mla = cache_bytes_per_token(dsv2)
    assert mla == (512 + 64) * 60 * 2
    # vs an MHA cache at the same head count and v_head_dim=128
    mha_equiv = 2 * dsv2.n_heads * dsv2.v_head_dim * dsv2.n_layers * 2
    assert mla < mha_equiv / 50  # the MLA compression claim (>50x here)

    assert cache_bytes_per_token(get_config("mamba2-780m")) == 0
    z = get_config("zamba2-1.2b")
    assert cache_bytes_per_token(z) == 2 * 32 * 64 * 7 * 2  # 7 shared sites


def test_greedy_decode_service_resolves_tuned_flash_record(tmp_path):
    """The serve-path dispatch contract: a store seeded with a tuned
    flash-attention record for the prefill shape signature is resolved
    (store_exact), and the dispatched path reproduces the un-dispatched
    tokens and logits."""
    from repro.dispatch import DispatchService, TuningRecord, TuningStore
    from repro.kernels.model_kernels import flash_attention_signature

    cfg = _cfg("qwen2-0.5b")
    params = init_params(cfg, KEY)
    B, S = 2, 6
    prompt = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    base_toks = greedy_decode(params, cfg, prompt, steps=4, max_len=12)
    base_logits, _ = forward(params, {"tokens": prompt}, cfg)

    store = TuningStore(str(tmp_path / "s"))
    # the GQA route dispatches per kv-head group: BH = batch * kv heads
    sig = flash_attention_signature(B * cfg.n_kv_heads, S, S, cfg.hd)
    assert store.put(TuningRecord("flash_attention", sig, "host",
                                  {"impl": "xla", "bq": 4, "bk": 4}, 1.0))
    svc = DispatchService(store)
    toks = greedy_decode(params, cfg, prompt, steps=4, max_len=12, service=svc)
    assert svc.stats["store_exact"] >= 1           # resolved by signature
    assert svc.stats["build_failed"] == 0          # tuned variant actually ran
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(base_toks))
    svc_logits, _ = forward(params, {"tokens": prompt}, cfg, service=svc)
    np.testing.assert_allclose(np.asarray(svc_logits), np.asarray(base_logits),
                               atol=1e-4, rtol=1e-4)


def test_greedy_decode_service_empty_store_uses_defaults(tmp_path):
    from repro.dispatch import DispatchService, TuningStore

    cfg = _cfg("qwen2-0.5b")
    params = init_params(cfg, KEY)
    prompt = jax.random.randint(KEY, (2, 6), 0, cfg.vocab_size)
    base = greedy_decode(params, cfg, prompt, steps=3, max_len=12)
    svc = DispatchService(TuningStore(str(tmp_path / "s")))
    toks = greedy_decode(params, cfg, prompt, steps=3, max_len=12, service=svc)
    assert svc.stats["store_default"] >= 1
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(base))


def test_greedy_decode_service_poisoned_record_degrades(tmp_path):
    from repro.dispatch import DispatchService, TuningRecord, TuningStore
    from repro.kernels.model_kernels import flash_attention_signature

    cfg = _cfg("qwen2-0.5b")
    params = init_params(cfg, KEY)
    B, S = 2, 6
    prompt = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    base = greedy_decode(params, cfg, prompt, steps=3, max_len=12)

    store = TuningStore(str(tmp_path / "s"))
    # the GQA route dispatches per kv-head group: BH = batch * kv heads
    sig = flash_attention_signature(B * cfg.n_kv_heads, S, S, cfg.hd)
    store.put(TuningRecord("flash_attention", sig, "host",
                           {"impl": "bogus", "bq": 4, "bk": 4}, 1.0))
    svc = DispatchService(store)
    toks = greedy_decode(params, cfg, prompt, steps=3, max_len=12, service=svc)
    # the static feasibility pass rejects impl="bogus" before any build is
    # attempted (invalid_choice:impl), so this counts as "infeasible", not
    # "build_failed" — degraded either way, did not raise
    assert svc.stats["infeasible"] >= 1
    assert svc.stats["build_failed"] == 0
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(base))
    # the poisoned record is quarantined, not re-served
    assert store.get("flash_attention", sig, "host") is None


def test_serve_step_emits_argmax_token():
    cfg = _cfg("mamba2-780m")
    params = init_params(cfg, KEY)
    from repro.models import init_cache
    cache = init_cache(cfg, 2, 8)
    serve = make_serve_step(cfg)
    tok = jnp.zeros((2, 1), jnp.int32)
    nxt, logits, _ = serve(params, cache, tok, 0)
    np.testing.assert_array_equal(
        np.asarray(nxt[:, 0]), np.asarray(jnp.argmax(logits, -1)))


# ---------------------------------------------------------------------------
# paged KV cache (continuous batching)
# ---------------------------------------------------------------------------


def test_cache_bytes_paged_rounding():
    from repro.serve import cache_bytes

    cfg = get_config("qwen2-0.5b")
    per = cache_bytes_per_token(cfg)
    assert cache_bytes(cfg, 2, 100) == per * 2 * 100
    # paged layout allocates whole pages: 100 tokens on 64-token pages = 128
    assert cache_bytes(cfg, 2, 100, page_size=64) == per * 2 * 128
    assert cache_bytes(cfg, 2, 128, page_size=64) == per * 2 * 128


def test_paged_cache_rejects_unsupported_archs():
    from repro.serve import PagedKVCache

    with pytest.raises(ValueError):
        PagedKVCache(_cfg("deepseek-v2-236b"), 2, 16)   # MLA latent cache
    with pytest.raises(ValueError):
        PagedKVCache(_cfg("gemma3-1b"), 2, 16)          # windowed ring cache
    with pytest.raises(ValueError):
        PagedKVCache(_cfg("qwen2-0.5b"), 2, 16, page_size=0)


def test_paged_decode_matches_per_request_greedy():
    """Continuous batching on bucketed views reproduces each request's
    solo greedy_decode tokens exactly — admit/view/writeback round-trip
    plus per-row positions change nothing."""
    from repro.serve import PagedKVCache

    cfg = _cfg("qwen2-0.5b")
    params = init_params(cfg, KEY)
    prompts = [jax.random.randint(jax.random.PRNGKey(31), (1, 5), 0,
                                  cfg.vocab_size),
               jax.random.randint(jax.random.PRNGKey(32), (1, 3), 0,
                                  cfg.vocab_size)]
    steps = 4
    pc = PagedKVCache(cfg, max_batch=4, max_len=16, page_size=8)
    base = [np.asarray(greedy_decode(params, cfg, p, steps=steps,
                                     max_len=pc.alloc)) for p in prompts]

    serve = make_serve_step(cfg)
    slots, toks = [0, 2], []
    for slot, p in zip(slots, prompts):
        logits, cache = prefill(params, {"tokens": p}, cfg, max_len=pc.alloc)
        pc.admit(slot, cache, p.shape[1])
        toks.append([int(jnp.argmax(logits[:, -1, :], -1)[0])])
    assert pc.active_slots() == slots
    cur = jnp.asarray([[t[-1]] for t in toks], jnp.int32)
    for _ in range(steps - 1):
        bucket = pc.seq_bucket(slots)
        view = pc.view(slots, bucket)
        nxt, _, view = serve(params, view, cur, pc.pos_vector(slots) + 1)
        pc.writeback(slots, bucket, view)
        pc.advance(slots)
        for i, t in enumerate(toks):
            t.append(int(nxt[i, 0]))
        cur = nxt
    for got, want in zip(toks, base):
        np.testing.assert_array_equal(np.asarray(got), want[0])


def test_paged_cache_accounting_and_telemetry(tmp_path):
    """stats() reports pages allocated (whole pages per sequence) vs tokens
    resident, and an attached cache surfaces under telemetry()['kv_cache']."""
    from repro.dispatch import DispatchService, TuningStore
    from repro.serve import PagedKVCache, init_cache

    cfg = _cfg("qwen2-0.5b")
    pc = PagedKVCache(cfg, max_batch=4, max_len=16, page_size=8)
    assert pc.alloc == 16
    pc.admit(1, init_cache(cfg, 1, 16, cfg.dtype), prompt_len=5)
    pc.admit(3, init_cache(cfg, 1, 16, cfg.dtype), prompt_len=11)
    st = pc.stats()
    assert st["slots_active"] == 2
    assert st["tokens_resident"] == 16
    assert st["pages_allocated"] == 1 + 2     # ceil(5/8) + ceil(11/8)
    assert st["page_occupancy"] == 16 / 24
    assert st["bytes_resident"] < st["bytes_allocated"] < st["bytes_backing"]
    # bucket covers the deepest sequence plus headroom, page-aligned
    assert pc.seq_bucket([1]) == 8
    assert pc.seq_bucket([1, 3]) == 16
    pc.release(1)
    assert pc.stats()["pages_allocated"] == 2

    svc = DispatchService(TuningStore(str(tmp_path / "s")))
    svc.attach_kv_cache(pc)
    assert svc.telemetry()["kv_cache"]["page_size"] == 8


def test_greedy_decode_service_resolves_tuned_decode_record(tmp_path):
    """The decode-path dispatch contract (ninth kernel): a store record at
    the decode signature — batch*kv_heads rows, seq = the cache bucket —
    resolves as store_exact, builds, and reproduces un-dispatched tokens."""
    from repro.dispatch import DispatchService, TuningRecord, TuningStore
    from repro.kernels.model_kernels import decode_attention_signature

    cfg = _cfg("qwen2-0.5b")
    params = init_params(cfg, KEY)
    B, S = 2, 6
    prompt = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    base = greedy_decode(params, cfg, prompt, steps=4, max_len=12)

    store = TuningStore(str(tmp_path / "s"))
    K = cfg.n_kv_heads
    sig = decode_attention_signature(B * K, cfg.n_heads // K, 12, cfg.hd)
    assert store.put(TuningRecord("decode_attention", sig, "host",
                                  {"impl": "xla", "bk": 8, "hg": 1,
                                   "page": 4}, 1.0))
    svc = DispatchService(store)
    toks = greedy_decode(params, cfg, prompt, steps=4, max_len=12, service=svc)
    assert svc.stats["store_exact"] >= 1
    assert svc.stats["build_failed"] == 0
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(base))


@pytest.mark.parametrize("arch,fill", [("qwen2-0.5b", "serve.prefill.fill"),
                                       ("mamba2-780m", "serve.prefill.replay")],
                         ids=["fused", "replay"])
def test_serving_layers_record_their_spans(arch, fill):
    """prefill, the dispatched serve step and the paged cache record the
    spans the benchmark reads, nested as the calls are: the forward and the
    cache fill (the fused write on a GQA config, the replay elsewhere) under
    ``serve.prefill``, with their attributes. A second prefill of the same
    shape runs the cached program: one ``serve.prefill`` with nothing below
    it, and the first call's logits and cache bit for bit."""
    from repro.dispatch import DispatchService
    from repro.obs import trace
    from repro.serve import PagedKVCache

    cfg = _cfg(arch)
    params = init_params(cfg, KEY)
    svc = DispatchService()
    paged = fill == "serve.prefill.fill"   # the paged cache takes GQA only
    old = trace.set_span_ring(trace.SpanRing())
    try:
        toks = jax.random.randint(KEY, (1, 5), 0, cfg.vocab_size)
        logits, cache = prefill(params, {"tokens": toks}, cfg, max_len=16,
                                service=svc)
        logits2, cache2 = prefill(params, {"tokens": toks}, cfg, max_len=16,
                                  service=svc)
        if paged:
            pc = PagedKVCache(cfg, max_batch=2, max_len=16, page_size=8)
            serve = make_serve_step(cfg, service=svc)
            pc.admit(1, cache, 5)
            view = pc.view([0, 1], 8)
            _, _, view = serve(params, view, jnp.zeros((2, 1), jnp.int32),
                               jnp.asarray([0, 5], jnp.int32))
            pc.writeback([0, 1], 8, view)
        spans = trace.recorded_spans()
    finally:
        trace.set_span_ring(old)
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    pre, again = sorted(by["serve.prefill"], key=lambda s: s.start_ns)
    for p in (pre, again):
        assert p.attrs == {"batch": 1, "prompt_len": 5} and p.parent == 0
    for half in ("serve.prefill.forward", fill):
        (h,) = by[half]
        assert h.parent == pre.id and pre.start_ns <= h.start_ns <= h.end_ns <= pre.end_ns
    other = {"serve.prefill.fill", "serve.prefill.replay"} - {fill}
    assert not other & set(by)
    # the same shape again: the cached executable, nothing traced or compiled
    assert not any(s.parent == again.id for s in spans)
    np.testing.assert_array_equal(np.asarray(logits2), np.asarray(logits))
    for a, b in zip(jax.tree_util.tree_leaves(cache2), jax.tree_util.tree_leaves(cache)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if not paged:
        return
    assert [s.attrs for s in by["kv.admit"]] == [{"slot": 1}]
    assert [s.attrs for s in by["kv.view"]] == [{"slots": 2, "bucket": 8}]
    assert [s.attrs for s in by["kv.writeback"]] == [{"slots": 2, "bucket": 8}]
    (step,) = by["serve.step"]
    assert step.attrs == {"batch": 2}
    # the step's first call traced and compiled under its own span
    assert any(s.parent == step.id for s in by["jax.compile"])


def _prefill_counts(registry, *names) -> tuple[float, ...]:
    counters = {c["name"]: c["value"] for c in registry.snapshot()["counters"]}
    names = names or ("serve_prefill_calls_total", "serve_prefill_traces_total")
    return tuple(counters.get(n, 0.0) for n in names)


@pytest.mark.parametrize("dispatched", [True, False], ids=["service", "no_service"])
def test_prefill_traces_once_per_prompt_length(dispatched):
    """Calls at three prompt lengths, twice each: six calls, three traces
    (the counters ``/metrics`` shows)."""
    from repro.dispatch import DispatchService

    cfg = _cfg("qwen2-0.5b")
    params = init_params(cfg, KEY)
    svc = DispatchService() if dispatched else None
    # a max_len no other test uses: the service-free program is process-wide
    max_len = 29
    old = set_registry(MetricsRegistry())
    try:
        for S in (3, 5, 7):
            for _ in range(2):
                toks = jax.random.randint(KEY, (1, S), 0, cfg.vocab_size)
                prefill(params, {"tokens": toks}, cfg, max_len=max_len, service=svc)
        assert _prefill_counts(get_registry()) == (6, 3)
    finally:
        set_registry(old)


def test_prefill_retraces_once_after_invalidate():
    """A hot swap (``DispatchService.invalidate``) makes the next prefill
    retrace against the new configs, once; later calls reuse that trace."""
    from repro.dispatch import DispatchService

    cfg = _cfg("qwen2-0.5b")
    params = init_params(cfg, KEY)
    svc = DispatchService()
    toks = jax.random.randint(KEY, (1, 4), 0, cfg.vocab_size)
    old = set_registry(MetricsRegistry())
    try:
        def call():
            return prefill(params, {"tokens": toks}, cfg, max_len=12, service=svc)

        first, _ = call()
        call()
        assert _prefill_counts(get_registry()) == (2, 1)
        svc.invalidate()
        again, _ = call()
        call()
        assert _prefill_counts(get_registry()) == (4, 2)
        np.testing.assert_array_equal(np.asarray(again), np.asarray(first))
    finally:
        set_registry(old)


# ---------------------------------------------------------------------------
# the prompt's K/V: written by the forward (GQA) or replayed (the rest)
# ---------------------------------------------------------------------------


def _greedy_from(params, cfg, logits, cache, start, steps=16):
    """``steps`` greedy tokens decoded from a prefilled cache."""
    serve = jax.jit(make_serve_step(cfg))
    tok = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)[:, None]
    out = []
    for t in range(steps):
        out.append(np.asarray(tok[:, 0]))
        tok, _, cache = serve(params, cache, tok, jnp.int32(start + t))
    return np.stack(out, 1)


def _mixtral_dense0():
    """Mixtral's GQA experts behind one leading dense layer: the structure of
    DeepSeek's stack without MLA."""
    return dataclasses.replace(_cfg("mixtral-8x7b"), n_layers=3,
                               first_dense_layers=1)


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "gemma3-1b", "qwen2-vl-7b",
                                  "mixtral-8x7b", "mixtral-dense0"])
def test_fused_prefill_matches_the_replay(arch):
    """On the GQA families the forward's own K/V fill the cache: QKV bias
    (qwen2), local:global windows over a full-length cache (gemma3, a prompt
    longer than its window), M-RoPE (qwen2-vl), dropless experts (mixtral,
    also behind a dense first layer). The cache equals the token-by-token
    replay's, the logits are the forward's, and 16 greedy steps decode the
    same tokens from either cache."""
    cfg = _mixtral_dense0() if arch == "mixtral-dense0" else _cfg(arch)
    params = init_params(cfg, KEY)
    B, S, max_len = 2, 12, 32
    toks = jax.random.randint(jax.random.PRNGKey(7), (B, S), 0, cfg.vocab_size)
    old = set_registry(MetricsRegistry())
    try:
        logits, cache = prefill(params, {"tokens": toks}, cfg, max_len=max_len)
        assert _prefill_counts(get_registry(), "serve_prefill_calls_total",
                               "serve_prefill_fused_total") == (1, 1)
    finally:
        set_registry(old)
    ref = jax.jit(lambda p, t: _replay(p, t, cfg, max_len))(params, toks)
    assert jax.tree_util.tree_structure(cache) == jax.tree_util.tree_structure(ref)
    for a, b in zip(jax.tree_util.tree_leaves(cache), jax.tree_util.tree_leaves(ref)):
        assert a.shape == b.shape and a.dtype == b.dtype == cfg.dtype
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=1e-5)
    want, _ = jax.jit(lambda p, t: forward(p, {"tokens": t}, cfg))(params, toks)
    assert logits.shape == (B, S, cfg.vocab_size) and logits.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want), rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(_greedy_from(params, cfg, logits, cache, S),
                                  _greedy_from(params, cfg, logits, ref, S))


def _ring():
    """A uniform sliding window shorter than the cache: a ring buffer."""
    return dataclasses.replace(_cfg("qwen2-0.5b"), sliding_window=8)


@pytest.mark.parametrize("arch", ["deepseek-v2-236b", "mamba2-780m", "zamba2-1.2b",
                                  "whisper-large-v3", "ring"])
def test_replay_families_keep_the_replay(arch):
    """MLA's latent cache, SSM state, the hybrid's shared attention, the
    encoder-decoder and a ring cache are still filled by the replay, and
    ``serve_prefill_fused_total`` counts none of their calls."""
    cfg = _ring() if arch == "ring" else _cfg(arch)
    params = init_params(cfg, KEY)
    B, S, max_len = 1, 12, 32
    toks = jax.random.randint(jax.random.PRNGKey(7), (B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks}
    if cfg.family == "audio":
        batch["enc_embed"] = jax.random.normal(KEY, (B, cfg.encoder_len, cfg.d_model))
    old = set_registry(MetricsRegistry())
    try:
        _, cache = prefill(params, batch, cfg, max_len=max_len)
        assert _prefill_counts(get_registry(), "serve_prefill_calls_total",
                               "serve_prefill_fused_total") == (1, 0)
    finally:
        set_registry(old)
    ref = jax.jit(lambda p, t: _replay(p, t, cfg, max_len))(params, toks)
    for a, b in zip(jax.tree_util.tree_leaves(cache), jax.tree_util.tree_leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _layer_scans(jaxpr):
    """The scan equations of a closed jaxpr, outermost first."""
    return [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]


def test_forward_and_loss_trace_without_the_kv_output():
    """The K/V output is opt-in below ``forward``: ``forward`` still returns
    (logits, aux), and the layer scan that ``loss_fn`` traces carries the
    residual stream and emits nothing per layer, where the prefill's
    ``forward_kv`` scan emits K and V."""
    from repro.models.model import forward_kv

    for cfg in (_cfg("qwen2-0.5b"), _cfg("mixtral-8x7b")):
        params = init_params(cfg, KEY)
        toks = jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size)
        batch = {"tokens": toks, "labels": toks}
        out = jax.eval_shape(lambda p: forward(p, batch, cfg), params)
        assert isinstance(out, tuple) and len(out) == 2
        assert out[0].shape == (2, 8, cfg.vocab_size) and out[0].dtype == jnp.float32
        assert out[1].shape == () and out[1].dtype == jnp.float32

        (scan,) = _layer_scans(jax.make_jaxpr(lambda p: loss_fn(p, batch, cfg))(params))
        assert len(scan.outvars) == scan.params["num_carry"]
        (scan_kv,) = _layer_scans(jax.make_jaxpr(
            lambda p: forward_kv(p, batch, cfg))(params))
        assert len(scan_kv.outvars) == scan_kv.params["num_carry"] + 2
