"""Plain reference of a sparse mixture-of-experts decoder with grouped-query
attention (the Mixtral family): float32, ``jax.numpy`` only, no cache, no
kernels, no batching, every matrix product at ``Precision.HIGHEST``.

It follows the Mixtral paper (arXiv:2401.04088, section 2) and the Hugging
Face ``MixtralForCausalLM`` code: token embedding; per layer an RMSNorm,
Q/K/V projections without bias, rotary position embedding on Q and K (the
rotate-half form, base ``rope_theta``), causal attention over the whole
context in which each group of query heads shares one key/value head, an
output projection, a residual add, then an RMSNorm, the expert layer and a
residual add; a final RMSNorm and an unembedding of its own (untied).

The expert layer: the router's logits over the ``E`` experts, a softmax,
the ``top_k`` largest probabilities renormalised over themselves (the same
weights as Mixtral's softmax over its top-2 logits). Every expert, a SwiGLU
``down(silu(gate(x)) * up(x))``, runs on every token, and its output is
weighted by its gate, which is zero where the token did not route to it:
plainer than a sparse layer, at ``E / top_k`` times its FLOPs. One expert's
matrices at a time are taken to float32.

Weights live in this module's own layout (:func:`make_weights`), made on
the device from a seed, one (layer, expert) matrix at a time. The
benchmark hands the same arrays to the system under test through
:func:`to_program`; this module imports nothing of the system under test.

``quant="fp8"`` is the control: every matrix product, the router's too,
takes its operands rounded to float8 e4m3 (weights scaled per tensor,
activations per row), as a lower-precision serving path would.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench.reference.dense_gqa import HI, _mm, _rms, _rope

__all__ = ["Dims", "make_weights", "to_program", "score", "score_routed"]


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int          # one expert's SwiGLU width
    experts: int
    top_k: int
    vocab: int
    rope_theta: float
    rms_eps: float

    @classmethod
    def from_config(cls, config: dict) -> "Dims":
        h = config["hf"]
        return cls(
            # the depth held here is the configuration's cut (``reduced``)
            layers=int(config["program"]["n_layers"]),
            d_model=int(h["hidden_size"]), heads=int(h["num_attention_heads"]),
            kv_heads=int(h["num_key_value_heads"]),
            head_dim=int(h["hidden_size"]) // int(h["num_attention_heads"]),
            d_ff=int(h["intermediate_size"]), experts=int(h["num_local_experts"]),
            top_k=int(h["num_experts_per_tok"]), vocab=int(h["vocab_size"]),
            rope_theta=float(h["rope_theta"]), rms_eps=float(h["rms_norm_eps"]))


def make_weights(dims: Dims, seed: int, dtype=jnp.bfloat16) -> dict:
    """Random weights in the serving dtype. Matrices are normal with
    standard deviation ``fan_in ** -0.5``; norm gains are ``1 + 0.1 *
    normal`` (float32). The router is rounded to the serving dtype and kept
    in float32. Each expert matrix is drawn on its own and written into its
    layer's stack in place, so no float32 copy of a stack is ever held."""
    L, d, H, K, hd, ff, E, V = (dims.layers, dims.d_model, dims.heads,
                                dims.kv_heads, dims.head_dim, dims.d_ff,
                                dims.experts, dims.vocab)
    key = jax.random.key(seed)

    def mat(k, shape, fan):
        return (jax.random.normal(k, shape, jnp.float32) * fan ** -0.5).astype(dtype)

    def small(key):
        ks = iter(jax.random.split(key, 12))
        gain = lambda shape: 1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)
        layers = {
            "q": mat(next(ks), (L, d, H * hd), d), "k": mat(next(ks), (L, d, K * hd), d),
            "v": mat(next(ks), (L, d, K * hd), d), "o": mat(next(ks), (L, H * hd, d), H * hd),
            "router": mat(next(ks), (L, d, E), d).astype(jnp.float32),
            "attn_norm": gain((L, d)), "mlp_norm": gain((L, d)),
        }
        return {"embed": mat(next(ks), (V, d), d), "unembed": mat(next(ks), (d, V), d),
                "norm": gain((d,)), "layers": layers}

    @functools.partial(jax.jit, donate_argnums=0, static_argnames=("fan",))
    def fill(stack, k, at, *, fan):
        one = mat(k, stack.shape[2:], fan)
        return jax.lax.dynamic_update_slice(stack, one[None, None], (at[0], at[1], 0, 0))

    w = jax.jit(small)(jax.random.fold_in(key, 0))
    for i, (name, shape, fan) in enumerate((("gate", (d, ff), d), ("up", (d, ff), d),
                                            ("down", (ff, d), ff))):
        stack = jnp.zeros((L, E) + shape, dtype)
        for layer in range(L):
            for e in range(E):
                k = jax.random.fold_in(key, 1 + (i * L + layer) * E + e)
                stack = fill(stack, k, jnp.asarray([layer, e], jnp.int32), fan=fan)
        w["layers"][name] = stack
    return w


def to_program(w: dict) -> dict:
    """The weights in the system's parameter layout, the same arrays: the
    system stores each norm gain as its offset from 1."""
    lw = w["layers"]
    layers = {"ln1": lw["attn_norm"] - 1.0, "ln2": lw["mlp_norm"] - 1.0,
              "wq": lw["q"], "wk": lw["k"], "wv": lw["v"], "wo": lw["o"],
              "moe": {"router": lw["router"], "wg": lw["gate"], "wu": lw["up"],
                      "wd": lw["down"]}}
    return {"embed": w["embed"], "unembed": w["unembed"],
            "final_norm": w["norm"] - 1.0, "layers": layers}


def _experts(h, router, experts, layer, dims: Dims, quant):
    """(S, d) normed hidden states -> ((S, d), (S,)): every expert on every
    token, weighted by its gate (zero where not routed), and the router's
    margin at each token: its ``top_k``-th largest logit less the next.
    ``experts`` are the stacked (L, E, ...) gate, up and down matrices; one
    expert's are read at a time."""
    logits = _mm(h, router, quant)                                   # (S, E)
    ranked = jax.lax.top_k(logits, dims.top_k + 1)[0]
    margin = ranked[:, dims.top_k - 1] - ranked[:, dims.top_k]
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, dims.top_k)
    top = top / top.sum(-1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(idx, dims.experts) * top[..., None], axis=1)

    def one(y, e):
        wg, wu, wd = (m[layer, e] for m in experts)
        g = jax.lax.dynamic_index_in_dim(gates, e, axis=1)             # (S, 1)
        return y + g * _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wu, quant),
                           wd, quant), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(dims.experts))
    return y, margin


def _hidden(w, tokens, dims: Dims, quant):
    """(S,) token ids -> (S, d_model) final-normed hidden states, and (S,)
    the least router margin of each position over the layers."""
    S = tokens.shape[0]
    H, K, hd = dims.heads, dims.kv_heads, dims.head_dim
    pos = jnp.arange(S)
    causal = pos[:, None] >= pos[None, :]
    x = w["embed"][tokens].astype(jnp.float32)
    lw = w["layers"]
    experts = (lw["gate"], lw["up"], lw["down"])

    def layer(x, i):
        p = {k: v[i] for k, v in lw.items() if k not in ("gate", "up", "down")}
        h = _rms(x, p["attn_norm"], dims.rms_eps)
        q = _rope(_mm(h, p["q"], quant).reshape(S, H, hd), pos, dims.rope_theta)
        k = _rope(_mm(h, p["k"], quant).reshape(S, K, hd), pos, dims.rope_theta)
        v = _mm(h, p["v"], quant).reshape(S, K, hd)
        q = q.reshape(S, K, H // K, hd)                      # head = kv * G + g
        s = jnp.einsum("skgd,tkd->kgst", q, k, precision=HI) * hd ** -0.5
        s = jnp.where(causal, s, -jnp.inf)
        a = jnp.einsum("kgst,tkd->skgd", jax.nn.softmax(s, axis=-1), v,
                       precision=HI)
        x = x + _mm(a.reshape(S, H * hd), p["o"], quant)
        h = _rms(x, p["mlp_norm"], dims.rms_eps)
        y, margin = _experts(h, p["router"], experts, i, dims, quant)
        return x + y, margin

    x, margins = jax.lax.scan(layer, x, jnp.arange(dims.layers))
    return _rms(x, w["norm"], dims.rms_eps), margins.min(0)


def score(w, tokens, pick, *, dims: Dims, quant=None, block: int = 256):
    """Reads the logits at every position of ``tokens`` (S,) without
    keeping them: returns ``(best, picked, top)``, each (S,): the largest
    logit, the logit of the id ``pick`` names at that position, and the id
    of the largest logit. ``S`` must be a multiple of ``block``; the
    unembedding runs one block of positions at a time."""
    return score_routed(w, tokens, pick, dims=dims, quant=quant, block=block)[:3]


@functools.partial(jax.jit, static_argnames=("dims", "quant", "block"))
def score_routed(w, tokens, pick, *, dims: Dims, quant=None, block: int = 256):
    """:func:`score`, and a fourth (S,) array: each position's least router
    margin over the layers (the ``top_k``-th largest router logit less the
    next). Where it is small, a rounding of the hidden state can route the
    token to another expert."""
    h, margin = _hidden(w, tokens, dims, quant)

    def one(args):
        hb, pb = args
        logits = _mm(hb, w["unembed"], quant)                # (block, V)
        return (logits.max(-1), jnp.take_along_axis(logits, pb[:, None], -1)[:, 0],
                jnp.argmax(logits, -1).astype(jnp.int32))

    S = tokens.shape[0]
    best, picked, top = jax.lax.map(one, (h.reshape(S // block, block, -1),
                                          pick.reshape(S // block, block)))
    return best.reshape(S), picked.reshape(S), top.reshape(S), margin
