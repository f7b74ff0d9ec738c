"""Layer blocks: GQA/MLA transformer layers (dense & MoE), Mamba2 layers,
encoder/decoder layers — init + train-time apply + decode-time apply.

All apply functions are scan-compatible: ``(x, (params_leafwise, per_layer
meta)) -> x`` with the config closed over, so whole stages lower to one
``lax.scan`` (essential for 60-layer dry-run compiles).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.attention import gqa_attention, gqa_decode
from repro.models.common import (
    ArchConfig,
    dense_init,
    mrope,
    rms_norm,
    rope,
    service_matmul,
)
from repro.models.mla import init_mla, mla_attention, mla_decode
from repro.models.moe import init_mlp, init_moe, mlp, moe_ffn
from repro.models.ssm import init_mamba2, mamba2_decode, mamba2_forward

__all__ = [
    "init_attn_layer", "attn_layer_train", "attn_layer_decode",
    "init_mamba_layer", "mamba_layer_train", "mamba_layer_decode",
    "init_cross_layer", "cross_layer_train", "cross_layer_decode",
    "layer_windows",
]


# ---------------------------------------------------------------------------
# per-layer attention-window schedule (uniform windows, gemma3 local:global)
# ---------------------------------------------------------------------------


def layer_windows(cfg: ArchConfig) -> np.ndarray:
    """(L,) window sizes; 0 means full/global attention."""
    L = cfg.n_layers
    if cfg.local_global_ratio:
        r = cfg.local_global_ratio
        w = np.full(L, cfg.sliding_window or 1024, np.int32)
        w[r::r + 1] = 0  # every (r+1)-th layer is global
        return w
    if cfg.sliding_window:
        return np.full(L, cfg.sliding_window, np.int32)
    return np.zeros(L, np.int32)


# ---------------------------------------------------------------------------
# transformer layer (GQA or MLA attention; dense MLP or MoE)
# ---------------------------------------------------------------------------


def init_attn_layer(key, cfg: ArchConfig, *, moe: bool, d_ff: int | None = None,
                    causal: bool = True) -> dict:
    d = cfg.d_model
    hd = cfg.hd
    dtype = cfg.dtype
    ks = jax.random.split(key, 10)
    p: dict = {"ln1": jnp.zeros((d,), jnp.float32), "ln2": jnp.zeros((d,), jnp.float32)}
    if cfg.attn_type == "mla":
        p["mla"] = init_mla(ks[0], cfg, dtype)
    else:
        p["wq"] = dense_init(ks[0], (d, cfg.n_heads * hd), 0, dtype)
        p["wk"] = dense_init(ks[1], (d, cfg.n_kv_heads * hd), 0, dtype)
        p["wv"] = dense_init(ks[2], (d, cfg.n_kv_heads * hd), 0, dtype)
        p["wo"] = dense_init(ks[3], (cfg.n_heads * hd, d), 0, dtype)
        if cfg.qkv_bias:
            p["bq"] = jnp.zeros((cfg.n_heads * hd,), jnp.float32)
            p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), jnp.float32)
            p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), jnp.float32)
        if cfg.qk_norm:
            p["q_norm"] = jnp.zeros((hd,), jnp.float32)
            p["k_norm"] = jnp.zeros((hd,), jnp.float32)
    if moe:
        p["moe"] = init_moe(ks[4], d, cfg.moe_d_ff or cfg.d_ff, cfg.n_experts,
                            cfg.n_shared_experts, dtype)
    else:
        p["mlp"] = init_mlp(ks[4], d, d_ff or cfg.d_ff, dtype)
    return p


def _qkv(p, h, cfg, positions):
    B, S, _ = h.shape
    hd = cfg.hd
    q = h @ p["wq"] + (p.get("bq", 0.0))
    k = h @ p["wk"] + (p.get("bk", 0.0))
    v = h @ p["wv"] + (p.get("bv", 0.0))
    q = q.reshape(B, S, cfg.n_heads, hd).astype(cfg.dtype)
    k = k.reshape(B, S, cfg.n_kv_heads, hd).astype(cfg.dtype)
    v = v.reshape(B, S, cfg.n_kv_heads, hd).astype(cfg.dtype)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    if positions is not None:
        if cfg.mrope:
            q = mrope(q, positions, cfg.rope_theta)
            k = mrope(k, positions, cfg.rope_theta)
        else:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_layer_train(p, x, *, cfg: ArchConfig, positions, window=None,
                     moe: bool = False, causal: bool = True, chunk: int = 512,
                     service=None, layer=None, emit_kv: bool = False):
    """Returns (x, aux). positions: (B,S) or (B,3,S) for M-RoPE; window: traced
    scalar (0 = full attention). ``service`` routes attention and the output
    projection through :mod:`repro.dispatch` tuned variants. ``layer``: the
    layer's index into the expert stacks in ``p["moe"]`` (``moe_ffn``).
    ``emit_kv`` (GQA only) returns (x, aux, {"k", "v"}) instead: the
    post-RoPE K/V of every position, ``(B, S, n_kv_heads, hd)`` in
    ``cfg.dtype``, the tensors attention read — what a prefill writes into
    the decode cache."""
    # window is a traced per-layer scalar inside the stage scan, so the flash
    # route is gated statically: only archs with no windowed layers qualify
    svc_attn = service if not (cfg.sliding_window or cfg.local_global_ratio) else None
    h = rms_norm(x, p["ln1"], cfg.rms_eps)
    if cfg.attn_type == "mla":
        attn = mla_attention(p["mla"], h, cfg,
                             positions if positions.ndim == 2 else positions[:, 0])
        x = x + attn
    else:
        q, k, v = _qkv(p, h, cfg, positions)
        o = gqa_attention(q, k, v, causal=causal, window=window, chunk=chunk,
                          f32=cfg.attn_f32, service=svc_attn)
        B, S = x.shape[:2]
        x = x + service_matmul(o.reshape(B, S, -1), p["wo"], service)

    h2 = rms_norm(x, p["ln2"], cfg.rms_eps)
    if moe:
        y, aux = moe_ffn(p["moe"], h2, top_k=cfg.top_k, service=service,
                         layer=layer)
    else:
        y, aux = mlp(p["mlp"], h2), jnp.zeros((), jnp.float32)
    if emit_kv:
        return x + y, aux, {"k": k, "v": v}
    return x + y, aux


def attn_layer_decode(p, x, cache, pos, *, cfg: ArchConfig, window=None,
                      moe: bool = False, mla_absorb: bool = True,
                      service=None, layer=None):
    """x: (B,1,d); cache: {'k': (B,S,K,hd), 'v': ...} or MLA latent cache.
    Returns (x, cache, aux). ``pos`` may be a (B,) vector for the GQA
    family (continuous batching: per-sequence decode positions; the cache
    insert becomes a per-row scatter). ``service`` routes the output
    projection through the tuned blocked matmul and — for archs with no
    windowed layers, where the per-layer window scalar is statically zero —
    single-token attention through the tuned ``decode_attention`` kernel.
    ``layer`` as in :func:`attn_layer_train`."""
    B = x.shape[0]
    h = rms_norm(x, p["ln1"], cfg.rms_eps)
    if cfg.attn_type == "mla":
        attn, cache = mla_decode(p["mla"], h, cache, cfg, pos, absorb=mla_absorb)
        x = x + attn
    else:
        # window rides through the layer scan as a traced scalar, so the
        # decode dispatch route is gated statically (cf. attn_layer_train)
        svc_attn = service if not (cfg.sliding_window or cfg.local_global_ratio) \
            else None
        positions = jnp.broadcast_to(jnp.asarray(pos).reshape(-1)[:, None], (B, 1))
        if cfg.mrope:
            positions = jnp.broadcast_to(positions[:, None, :], (B, 3, 1))
        q, k, v = _qkv(p, h, cfg, positions)
        S_alloc = cache["k"].shape[1]
        ring = bool(cfg.sliding_window) and not cfg.local_global_ratio \
            and S_alloc == cfg.sliding_window
        if jnp.ndim(pos) == 0:
            slot = jnp.mod(pos, S_alloc) if ring else pos
            cache = {
                "k": jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0)),
                "v": jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0)),
            }
        else:
            # per-sequence positions: row b writes its own slot
            slots = jnp.mod(pos, S_alloc) if ring else pos
            rows = jnp.arange(B)
            cache = {
                "k": cache["k"].at[rows, slots].set(k[:, 0].astype(cache["k"].dtype)),
                "v": cache["v"].at[rows, slots].set(v[:, 0].astype(cache["v"].dtype)),
            }
        o = gqa_decode(q, cache["k"], cache["v"], pos,
                       window=None if svc_attn is not None else window,
                       ring=ring, service=svc_attn)
        x = x + service_matmul(o.reshape(B, 1, -1), p["wo"], service)

    h2 = rms_norm(x, p["ln2"], cfg.rms_eps)
    if moe:
        y, aux = moe_ffn(p["moe"], h2, top_k=cfg.top_k, service=service,
                         layer=layer)
    else:
        y, aux = mlp(p["mlp"], h2), jnp.zeros((), jnp.float32)
    return x + y, cache, aux


# ---------------------------------------------------------------------------
# mamba2 layer (pre-norm residual)
# ---------------------------------------------------------------------------


def init_mamba_layer(key, cfg: ArchConfig) -> dict:
    return {
        "ln": jnp.zeros((cfg.d_model,), jnp.float32),
        "mixer": init_mamba2(
            key, cfg.d_model, expand=cfg.ssm_expand, head_dim=cfg.ssm_head_dim,
            n_state=cfg.ssm_state, conv_width=cfg.ssm_conv_width, dtype=cfg.dtype,
        ),
    }


def mamba_layer_train(p, x, *, cfg: ArchConfig, chunk: int = 64):
    h = rms_norm(x, p["ln"], cfg.rms_eps)
    return x + mamba2_forward(p["mixer"], h, cfg, chunk=chunk)


def mamba_layer_decode(p, x, cache, *, cfg: ArchConfig):
    h = rms_norm(x, p["ln"], cfg.rms_eps)
    y, cache = mamba2_decode(p["mixer"], h, cache, cfg)
    return x + y, cache


# ---------------------------------------------------------------------------
# encoder-decoder (whisper): decoder layer with cross-attention
# ---------------------------------------------------------------------------


def init_cross_layer(key, cfg: ArchConfig) -> dict:
    d = cfg.d_model
    hd = cfg.hd
    dtype = cfg.dtype
    ks = jax.random.split(key, 9)
    p = init_attn_layer(ks[0], cfg, moe=False)
    p["ln_x"] = jnp.zeros((d,), jnp.float32)
    p["xq"] = dense_init(ks[1], (d, cfg.n_heads * hd), 0, dtype)
    p["xk"] = dense_init(ks[2], (d, cfg.n_kv_heads * hd), 0, dtype)
    p["xv"] = dense_init(ks[3], (d, cfg.n_kv_heads * hd), 0, dtype)
    p["xo"] = dense_init(ks[4], (cfg.n_heads * hd, d), 0, dtype)
    return p


def _cross_attend(p, x, enc_k, enc_v, cfg):
    B, S, _ = x.shape
    hd = cfg.hd
    h = rms_norm(x, p["ln_x"], cfg.rms_eps)
    q = (h @ p["xq"]).reshape(B, S, cfg.n_heads, hd).astype(cfg.dtype)
    o = gqa_attention(q, enc_k, enc_v, causal=False, window=None)
    return x + o.reshape(B, S, -1) @ p["xo"]


def cross_layer_train(p, x, enc_kv, *, cfg: ArchConfig, positions):
    """Decoder layer: causal self-attn, cross-attn to encoder K/V, MLP."""
    h = rms_norm(x, p["ln1"], cfg.rms_eps)
    q, k, v = _qkv(p, h, cfg, positions)
    B, S = x.shape[:2]
    o = gqa_attention(q, k, v, causal=True, window=None)
    x = x + o.reshape(B, S, -1) @ p["wo"]
    x = _cross_attend(p, x, enc_kv["k"], enc_kv["v"], cfg)
    y = mlp(p["mlp"], rms_norm(x, p["ln2"], cfg.rms_eps))
    return x + y


def cross_layer_decode(p, x, cache, enc_kv, pos, *, cfg: ArchConfig):
    B = x.shape[0]
    h = rms_norm(x, p["ln1"], cfg.rms_eps)
    positions = jnp.full((B, 1), pos)
    q, k, v = _qkv(p, h, cfg, positions)
    cache = {
        "k": jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, pos, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, pos, 0, 0)),
    }
    o = gqa_decode(q, cache["k"], cache["v"], pos)
    x = x + o.reshape(B, 1, -1) @ p["wo"]
    x = _cross_attend(p, x, enc_kv["k"], enc_kv["v"], cfg)
    y = mlp(p["mlp"], rms_norm(x, p["ln2"], cfg.rms_eps))
    return x + y, cache
