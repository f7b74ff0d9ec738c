"""Per-architecture smoke tests (deliverable f): REDUCED config of each
family — forward + one train step on CPU, asserting shapes and finiteness —
plus family-specific invariants (SSD vs recurrence, MLA absorb equivalence,
ring cache)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_reduced
from repro.models import (
    decode_step,
    forward,
    init_cache,
    init_params,
    loss_fn,
)
from repro.train import init_train_state, make_train_step

KEY = jax.random.PRNGKey(0)


def _f32(cfg):
    return dataclasses.replace(cfg, dtype=jnp.float32)


def _batch(cfg, B=2, S=16):
    b = {
        "tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size),
    }
    if cfg.family == "audio":
        b["enc_embed"] = jax.random.normal(KEY, (B, cfg.encoder_len, cfg.d_model))
    return b


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_smoke_forward_and_decode(arch):
    cfg = _f32(get_reduced(arch))
    params = init_params(cfg, KEY)
    batch = _batch(cfg)
    logits, aux = forward(params, batch, cfg, ssm_chunk=8)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    total, metrics = loss_fn(params, batch, cfg, ssm_chunk=8)
    assert bool(jnp.isfinite(total))

    cache = init_cache(cfg, 2, 32)
    lg, cache2 = decode_step(params, cache, batch["tokens"][:, :1], 0, cfg)
    assert lg.shape == (2, cfg.vocab_size)
    assert bool(jnp.isfinite(lg).all())


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mamba2-780m", "mixtral-8x7b",
                                  "deepseek-v2-236b", "zamba2-1.2b"])
def test_smoke_train_step(arch):
    cfg = _f32(get_reduced(arch))
    params = init_params(cfg, KEY)
    opt = init_train_state(params)
    step = make_train_step(cfg, lr=1e-3, ssm_chunk=8)
    batch = _batch(cfg)
    p2, o2, metrics = jax.jit(step)(params, opt, batch)
    assert bool(jnp.isfinite(metrics["total"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    assert int(o2["step"]) == 1
    # parameters actually moved
    delta = sum(float(jnp.abs(a - b).sum()) for a, b in zip(
        jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(p2)))
    assert delta > 0


def test_config_registry_complete():
    assert len(ARCHS) == 10
    for arch in ARCHS:
        cfg = get_config(arch)
        assert cfg.name == arch
        red = get_reduced(arch)
        assert red.family == cfg.family
        assert red.param_count() < cfg.param_count()


def test_param_counts_match_public_sizes():
    """Analytic N must land near the published sizes (ours differ only via
    documented substitutions like gated MLPs — see DESIGN.md)."""
    expected = {
        "qwen2-vl-7b": 7.6e9, "deepseek-v2-236b": 236e9, "mixtral-8x7b": 46.7e9,
        "mamba2-780m": 0.78e9, "gemma3-1b": 1.0e9, "qwen2-0.5b": 0.49e9,
        "zamba2-1.2b": 1.2e9,
    }
    for arch, n in expected.items():
        got = get_config(arch).param_count()
        assert 0.7 < got / n < 1.4, (arch, got, n)


def test_moe_active_params_smaller():
    for arch in ("deepseek-v2-236b", "mixtral-8x7b"):
        cfg = get_config(arch)
        assert cfg.active_param_count() < 0.35 * cfg.param_count()


def test_decode_matches_forward_teacher_forcing():
    """Sequential decode over a prompt must reproduce forward()'s logits —
    the cache path's end-to-end correctness check."""
    for arch in ("qwen2-0.5b", "mamba2-780m", "gemma3-1b"):
        cfg = _f32(get_reduced(arch))
        params = init_params(cfg, KEY)
        B, S = 2, 12
        toks = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, cfg.vocab_size)
        ref_logits, _ = forward(params, {"tokens": toks}, cfg, ssm_chunk=4)
        cache = init_cache(cfg, B, S + 4)
        outs = []
        for t in range(S):
            lg, cache = decode_step(params, cache, toks[:, t:t + 1], t, cfg)
            outs.append(lg)
        got = jnp.stack(outs, axis=1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref_logits),
                                   atol=2e-2, rtol=2e-2), arch


def test_mla_absorb_equivalence():
    """DeepSeek decode: absorbed and naive schedules are the same math."""
    cfg = _f32(get_reduced("deepseek-v2-236b"))
    params = init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (2, 1), 0, cfg.vocab_size)
    c1 = init_cache(cfg, 2, 8)
    c2 = init_cache(cfg, 2, 8)
    lg_a, _ = decode_step(params, c1, toks, 0, cfg, mla_absorb=True)
    lg_n, _ = decode_step(params, c2, toks, 0, cfg, mla_absorb=False)
    np.testing.assert_allclose(np.asarray(lg_a), np.asarray(lg_n),
                               atol=1e-3, rtol=1e-3)


def test_ring_cache_matches_full_attention_within_window():
    """The windowed ring KV cache must reproduce full forward logits even
    after the ring wraps. Uses a dense arch given a uniform window."""
    cfg = dataclasses.replace(_f32(get_reduced("qwen2-0.5b")), sliding_window=8)
    params = init_params(cfg, KEY)
    B, S = 1, 20  # exceeds the 8-token window -> ring wraps twice
    toks = jax.random.randint(jax.random.PRNGKey(9), (B, S), 0, cfg.vocab_size)
    cache = init_cache(cfg, B, S)
    assert cache["layers"]["k"].shape[2] == cfg.sliding_window  # ring alloc
    ring_logits = []
    for t in range(S):
        lg, cache = decode_step(params, cache, toks[:, t:t + 1], t, cfg)
        ring_logits.append(lg)
    ref, _ = forward(params, {"tokens": toks}, cfg)
    got = jnp.stack(ring_logits, 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)


def test_moe_capacity_drops_are_decode_train_semantic_difference():
    """The expert layer is dropless: batched forward and sequential decode
    agree for an MoE arch with no capacity to set (the GShard layer it
    replaced dropped tokens over capacity in the batched path only)."""
    from repro.models.model import MOE_AUX_COEF  # noqa: F401 (import check)

    cfg = _f32(get_reduced("mixtral-8x7b"))
    params = init_params(cfg, KEY)
    toks = jax.random.randint(jax.random.PRNGKey(9), (1, 6), 0, cfg.vocab_size)
    ref, _ = forward(params, {"tokens": toks}, cfg)
    cache = init_cache(cfg, 1, 8)
    outs = []
    for t in range(6):
        lg, cache = decode_step(params, cache, toks[:, t:t + 1], t, cfg)
        outs.append(lg)
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)


def test_gemma3_local_global_pattern():
    from repro.models.blocks import layer_windows
    cfg = get_config("gemma3-1b")
    w = layer_windows(cfg)
    assert len(w) == cfg.n_layers
    assert (w == 0).sum() == cfg.n_layers // (cfg.local_global_ratio + 1)
    assert set(w[w > 0]) == {cfg.sliding_window}


def test_moe_group_size_invariance():
    """The dropless layer is semantics-preserving whatever the grouping of
    tokens into a batch: every (batch, seq) split of the same 64 tokens
    yields the same output (float32, summation order alone differs)."""
    from repro.models.moe import init_moe, moe_ffn

    p = init_moe(jax.random.PRNGKey(7), 16, 32, n_experts=4, n_shared=0,
                 dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 32, 16))
    outs = []
    for shape in ((2, 32), (8, 8), (64, 1), (1, 64)):
        y, _ = moe_ffn(p, x.reshape(*shape, 16), top_k=2)
        outs.append(np.asarray(y).reshape(2, 32, 16))
    for o in outs[1:]:
        np.testing.assert_allclose(outs[0], o, atol=1e-5, rtol=1e-5)


def test_whisper_decode_teacher_forcing():
    """Enc-dec path: sequential decode (self-cache + fixed cross K/V) must
    reproduce the batched decoder forward."""
    cfg = _f32(get_reduced("whisper-large-v3"))
    params = init_params(cfg, KEY)
    B, S = 1, 10
    toks = jax.random.randint(jax.random.PRNGKey(11), (B, S), 0, cfg.vocab_size)
    enc = jax.random.normal(jax.random.PRNGKey(12), (B, cfg.encoder_len, cfg.d_model))
    ref, _ = forward(params, {"tokens": toks, "enc_embed": enc}, cfg)

    # fill the cross cache the way prefill would: encoder output per layer
    from repro.models import blocks as BB
    from repro.models.common import rms_norm as _rn
    cache = init_cache(cfg, B, S + 2)
    enc_out = enc
    enc_pos = jnp.broadcast_to(jnp.arange(cfg.encoder_len)[None, :],
                               (B, cfg.encoder_len))
    for li in range(cfg.n_encoder_layers):
        p_l = jax.tree_util.tree_map(lambda a: a[li], params["enc_layers"])
        enc_out, _ = BB.attn_layer_train(p_l, enc_out, cfg=cfg,
                                         positions=enc_pos, window=None,
                                         moe=False, causal=False)
    hd = cfg.hd
    eks, evs = [], []
    for li in range(cfg.n_layers):
        p_l = jax.tree_util.tree_map(lambda a: a[li], params["dec_layers"])
        eks.append((enc_out @ p_l["xk"]).reshape(B, cfg.encoder_len,
                                                 cfg.n_kv_heads, hd))
        evs.append((enc_out @ p_l["xv"]).reshape(B, cfg.encoder_len,
                                                 cfg.n_kv_heads, hd))
    cache["cross"] = {"k": jnp.stack(eks), "v": jnp.stack(evs)}

    outs = []
    for t in range(S):
        lg, cache = decode_step(params, cache, toks[:, t:t + 1], t, cfg)
        outs.append(lg)
    got = jnp.stack(outs, 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=3e-2, rtol=3e-2)
