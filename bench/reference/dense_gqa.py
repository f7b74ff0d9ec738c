"""Plain reference of a dense decoder with grouped-query attention (the
Qwen2 family): float32, ``jax.numpy`` only, no cache, no kernels, no
batching, every matrix product at ``Precision.HIGHEST``.

It follows the published Qwen2 description (arXiv:2407.10671 and the
Hugging Face ``Qwen2ForCausalLM`` code): token embedding; per layer an
RMSNorm, Q/K/V projections with bias, rotary position embedding on Q and K
(the rotate-half form, base ``rope_theta``), causal attention in which each
group of query heads shares one key/value head, an output projection
without bias, a residual add, then an RMSNorm, a SwiGLU MLP
(``down(silu(gate(x)) * up(x))``) and a residual add; a final RMSNorm and
the unembedding, tied to the embedding where the configuration says so.

Weights live in this module's own layout (:func:`make_weights`), made on
the device from a seed in one jitted call. The benchmark hands the same
arrays to the system under test through its driver's adapter; this module
imports nothing of the system under test.

``quant="fp8"`` is the control: every matrix product takes its operands
rounded to float8 e4m3 (weights scaled per tensor, activations per row), as
a lower-precision serving path would.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

__all__ = ["Dims", "make_weights", "score"]

HI = jax.lax.Precision.HIGHEST
_FP8_MAX = 448.0   # largest finite float8 e4m3fn


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    rms_eps: float
    qkv_bias: bool
    tied: bool

    @classmethod
    def from_config(cls, config: dict) -> "Dims":
        h = config["hf"]
        return cls(
            layers=int(h["num_hidden_layers"]), d_model=int(h["hidden_size"]),
            heads=int(h["num_attention_heads"]),
            kv_heads=int(h["num_key_value_heads"]),
            head_dim=int(h["hidden_size"]) // int(h["num_attention_heads"]),
            d_ff=int(h["intermediate_size"]), vocab=int(h["vocab_size"]),
            rope_theta=float(h["rope_theta"]), rms_eps=float(h["rms_norm_eps"]),
            qkv_bias=bool(config["qkv_bias"]),
            tied=bool(h["tie_word_embeddings"]))


def make_weights(dims: Dims, seed: int, dtype=jnp.bfloat16) -> dict:
    """Random weights in the serving dtype, one jitted call on the device.

    Matrices are normal with standard deviation ``fan_in ** -0.5``; norm
    gains are ``1 + 0.1 * normal`` and biases ``0.1 * normal`` (float32), so
    that a path which dropped a gain or a bias would show."""
    if not dims.tied:
        raise NotImplementedError("untied unembedding")
    L, d, H, K, hd, ff, V = (dims.layers, dims.d_model, dims.heads,
                             dims.kv_heads, dims.head_dim, dims.d_ff, dims.vocab)
    shapes = {
        "q": ((L, d, H * hd), d), "k": ((L, d, K * hd), d),
        "v": ((L, d, K * hd), d), "o": ((L, H * hd, d), H * hd),
        "gate": ((L, d, ff), d), "up": ((L, d, ff), d), "down": ((L, ff, d), ff),
    }

    def make(key):
        ks = iter(jax.random.split(key, 16))
        mat = lambda shape, fan: (jax.random.normal(next(ks), shape, jnp.float32)
                                  * fan ** -0.5).astype(dtype)
        gain = lambda shape: 1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)
        bias = lambda shape: 0.1 * jax.random.normal(next(ks), shape, jnp.float32)
        layers = {name: mat(shape, fan) for name, (shape, fan) in shapes.items()}
        layers["attn_norm"] = gain((L, d))
        layers["mlp_norm"] = gain((L, d))
        if dims.qkv_bias:
            layers["q_bias"] = bias((L, H * hd))
            layers["k_bias"] = bias((L, K * hd))
            layers["v_bias"] = bias((L, K * hd))
        return {"embed": mat((V, d), d), "norm": gain((d,)), "layers": layers}

    return jax.jit(make)(jax.random.key(seed))


def _q8(x, axis):
    """Round to float8 e4m3 with a scale that maps the largest magnitude
    along ``axis`` (``None``: the whole tensor) to the format's maximum."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None) / _FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _q8(x, -1), _q8(w, None)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x: (S, heads, hd). Rotate-half rotary embedding."""
    hd = x.shape[-1]
    inv = theta ** -(jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv            # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _hidden(w, tokens, dims: Dims, quant):
    """(S,) token ids -> (S, d_model) final-normed hidden states."""
    S = tokens.shape[0]
    H, K, hd = dims.heads, dims.kv_heads, dims.head_dim
    pos = jnp.arange(S)
    causal = pos[:, None] >= pos[None, :]
    x = w["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        h = _rms(x, p["attn_norm"], dims.rms_eps)
        q, k, v = _mm(h, p["q"], quant), _mm(h, p["k"], quant), _mm(h, p["v"], quant)
        if dims.qkv_bias:
            q, k, v = q + p["q_bias"], k + p["k_bias"], v + p["v_bias"]
        q = _rope(q.reshape(S, H, hd), pos, dims.rope_theta)
        k = _rope(k.reshape(S, K, hd), pos, dims.rope_theta)
        v = v.reshape(S, K, hd)
        q = q.reshape(S, K, H // K, hd)                      # head = kv * G + g
        s = jnp.einsum("skgd,tkd->kgst", q, k, precision=HI) * hd ** -0.5
        s = jnp.where(causal, s, -jnp.inf)
        a = jnp.einsum("kgst,tkd->skgd", jax.nn.softmax(s, axis=-1), v,
                       precision=HI)
        x = x + _mm(a.reshape(S, H * hd), p["o"], quant)
        h = _rms(x, p["mlp_norm"], dims.rms_eps)
        x = x + _mm(jax.nn.silu(_mm(h, p["gate"], quant)) * _mm(h, p["up"], quant),
                    p["down"], quant)
        return x, None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    return _rms(x, w["norm"], dims.rms_eps)


@functools.partial(jax.jit, static_argnames=("dims", "quant", "block"))
def score(w, tokens, pick, *, dims: Dims, quant=None, block: int = 256):
    """Reads the logits at every position of ``tokens`` (S,) without
    keeping them: returns ``(best, picked, top)``, each (S,): the largest
    logit, the logit of the id ``pick`` names at that position, and the id
    of the largest logit. ``S`` must be a multiple of ``block``; the
    unembedding runs one block of positions at a time."""
    h = _hidden(w, tokens, dims, quant)
    E = w["embed"]

    def one(args):
        hb, pb = args
        logits = _mm(hb, E.T, quant)                         # (block, V)
        return (logits.max(-1), jnp.take_along_axis(logits, pb[:, None], -1)[:, 0],
                jnp.argmax(logits, -1).astype(jnp.int32))

    S = tokens.shape[0]
    best, picked, top = jax.lax.map(one, (h.reshape(S // block, block, -1),
                                          pick.reshape(S // block, block)))
    return best.reshape(S), picked.reshape(S), top.reshape(S)
