"""Model assembly: init / forward / loss / decode for every assigned family.

Layers are stacked (leading L axis) and driven by ``lax.scan`` so a 60-layer
model lowers as one scanned block — the property that keeps the 512-device
dry-run compiles tractable. Heterogeneous stacks (DeepSeek's leading dense
layer, Zamba2's shared attention block) become separate stages around the
scan.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import blocks as B
from repro.models import moe as MOE
from repro.models.common import ArchConfig, dense_init, rms_norm, service_matmul
from repro.models.mla import init_mla_cache
from repro.models.ssm import init_ssm_cache

__all__ = [
    "init_params", "abstract_params", "forward", "forward_kv", "loss_fn",
    "init_cache", "cache_len", "decode_step", "make_batch_positions",
]

MOE_AUX_COEF = 0.01


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _stack_init(init_one: Callable, key, n: int):
    return jax.vmap(init_one)(jax.random.split(key, n))


def init_params(cfg: ArchConfig, key) -> dict:
    ks = jax.random.split(key, 8)
    p: dict = {
        "embed": dense_init(ks[0], (cfg.vocab_size, cfg.d_model), 1, cfg.dtype),
        "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(ks[6], (cfg.d_model, cfg.vocab_size), 0, cfg.dtype)

    fam = cfg.family
    if fam in ("dense", "vlm"):
        p["layers"] = _stack_init(
            lambda k: B.init_attn_layer(k, cfg, moe=False), ks[1], cfg.n_layers)
    elif fam == "moe":
        n_moe = cfg.n_layers - cfg.first_dense_layers
        if cfg.first_dense_layers:
            # DeepSeek's leading dense layer uses the conventional wide FFN
            p["dense0"] = B.init_attn_layer(ks[2], cfg, moe=False,
                                            d_ff=_dense_ff(cfg))
        p["layers"] = _stack_init(
            lambda k: B.init_attn_layer(k, cfg, moe=True), ks[1], n_moe)
    elif fam == "ssm":
        p["layers"] = _stack_init(
            lambda k: B.init_mamba_layer(k, cfg), ks[1], cfg.n_layers)
    elif fam == "hybrid":
        p["layers"] = _stack_init(
            lambda k: B.init_mamba_layer(k, cfg), ks[1], cfg.n_layers)
        p["shared_attn"] = B.init_attn_layer(ks[3], cfg, moe=False)
    elif fam == "audio":
        p["enc_layers"] = _stack_init(
            lambda k: B.init_attn_layer(k, cfg, moe=False), ks[1],
            cfg.n_encoder_layers)
        p["dec_layers"] = _stack_init(
            lambda k: B.init_cross_layer(k, cfg), ks[4], cfg.n_layers)
    else:
        raise ValueError(f"unknown family {fam!r}")
    return p


def _dense_ff(cfg: ArchConfig) -> int:
    # DeepSeek-V2's dense layers use the wide FFN (12288), not the expert width
    return 12288 if cfg.name.startswith("deepseek") else cfg.d_ff


def abstract_params(cfg: ArchConfig):
    """ShapeDtypeStruct pytree of the parameters — no allocation (dry-run)."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def make_batch_positions(cfg: ArchConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    Bsz, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None, :], (Bsz, S))
    if cfg.mrope:
        return jnp.broadcast_to(pos[:, None, :], (Bsz, 3, S))
    return pos


def _cst(x, spec):
    """Activation sharding constraint (no-op when spec is None). Pinning the
    residual stream to (batch-axes, None, None) keeps XLA's SPMD propagation
    on the Megatron layout — without it, CPU SPMD happily replicates the
    batch and all-reduces logits (observed: an 80 GB collective)."""
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def _maybe_remat(fn, remat: str):
    if remat == "none":
        return fn
    if remat == "full":
        return jax.checkpoint(fn)
    if remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    raise ValueError(f"unknown remat policy {remat!r}")


def _experts_apart(params_stack, moe: bool):
    """The scanned layer parameters without the expert matrices, the layer
    indices to scan beside them (None without experts), and a function that
    puts the matrices, as whole stacks, back into a layer's slice:
    ``moe_ffn`` indexes them by layer, as a slice handed to its Pallas call
    would be copied out on every call."""
    if not moe:
        return params_stack, None, lambda p_l: p_l
    mats = {k: params_stack["moe"][k] for k in MOE.EXPERT_MATS}
    rest = {k: v for k, v in params_stack["moe"].items() if k not in mats}
    return ({**params_stack, "moe": rest}, jnp.arange(mats["wg"].shape[0]),
            lambda p_l: {**p_l, "moe": {**p_l["moe"], **mats}})


def _scan_attn_stage(params_stack, x, windows, *, cfg, positions, moe, remat,
                     chunk, act_spec=None, service=None, emit_kv=False):
    """Returns (x, aux, kv): ``kv`` is None, or with ``emit_kv`` every
    layer's K/V (``attn_layer_train``) stacked on a leading L axis."""
    params_stack, layers, whole = _experts_apart(params_stack, moe)

    def body(carry, xs):
        x, aux = carry
        p_l, w_l, i = xs
        x, a, *kv = B.attn_layer_train(whole(p_l), x, cfg=cfg, positions=positions,
                                       window=w_l, moe=moe, chunk=chunk,
                                       service=service, layer=i, emit_kv=emit_kv)
        x = _cst(x, act_spec)
        return (x, aux + a), (kv[0] if emit_kv else None)

    body = _maybe_remat(body, remat)
    (x, aux), kv = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                (params_stack, windows, layers))
    return x, aux, kv


def forward(params: dict, batch: dict, cfg: ArchConfig, *, remat: str = "none",
            attn_chunk: int = 512, ssm_chunk: int = 64, act_spec=None,
            logits_spec=None, service=None):
    """Returns (logits, aux_loss). ``service`` (a
    :class:`repro.dispatch.DispatchService`) routes attention and the big
    matmul call sites through tuned, store-resolved kernel variants."""
    logits, aux, _ = _forward(params, batch, cfg, remat=remat,
                              attn_chunk=attn_chunk, ssm_chunk=ssm_chunk,
                              act_spec=act_spec, logits_spec=logits_spec,
                              service=service)
    return logits, aux


def forward_kv(params: dict, batch: dict, cfg: ArchConfig, **fw_kw):
    """:func:`forward`'s logits and the post-RoPE K/V of every attention
    layer, as attention read them (GQA families: dense, vlm, moe). Returns
    (logits, kv): ``kv`` has :func:`init_cache`'s structure, with the
    prompt's S positions where the cache has ``cache_len`` — what a prefill
    writes into its cache in place of replaying the prompt."""
    logits, _, kv = _forward(params, batch, cfg, emit_kv=True, **fw_kw)
    return logits, kv


def _forward(params: dict, batch: dict, cfg: ArchConfig, *, remat: str = "none",
             attn_chunk: int = 512, ssm_chunk: int = 64, act_spec=None,
             logits_spec=None, service=None, emit_kv: bool = False):
    """:func:`forward`, also returning the K/V that ``emit_kv`` asks of the
    GQA layers (None without it)."""
    tokens = batch["tokens"]
    Bsz, S = tokens.shape
    x = _cst(params["embed"][tokens].astype(cfg.dtype), act_spec)
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.dtype)
    positions = batch.get("positions", None)
    if positions is None:
        positions = make_batch_positions(cfg, tokens)

    aux = jnp.zeros((), jnp.float32)
    kv = None
    fam = cfg.family

    if fam in ("dense", "vlm"):
        windows = jnp.asarray(B.layer_windows(cfg))
        x, aux, layers_kv = _scan_attn_stage(
            params["layers"], x, windows, cfg=cfg, positions=positions,
            moe=False, remat=remat, chunk=attn_chunk, act_spec=act_spec,
            service=service, emit_kv=emit_kv)
        if emit_kv:
            kv = {"layers": layers_kv}
    elif fam == "moe":
        kv = {} if emit_kv else None
        if "dense0" in params:
            x, a0, *kv0 = B.attn_layer_train(params["dense0"], x, cfg=cfg,
                                             positions=positions, window=None,
                                             moe=False, chunk=attn_chunk,
                                             service=service, emit_kv=emit_kv)
            aux = aux + a0
            if emit_kv:
                kv["dense0"] = kv0[0]
        windows = jnp.asarray(B.layer_windows(cfg)[cfg.first_dense_layers:])
        x, a, layers_kv = _scan_attn_stage(
            params["layers"], x, windows, cfg=cfg, positions=positions,
            moe=True, remat=remat, chunk=attn_chunk, act_spec=act_spec,
            service=service, emit_kv=emit_kv)
        aux = aux + a
        if emit_kv:
            kv["layers"] = layers_kv
    elif fam in ("ssm", "hybrid"):
        def mamba_body(x, p_l):
            x = B.mamba_layer_train(p_l, x, cfg=cfg, chunk=ssm_chunk)
            return _cst(x, act_spec), None

        mamba_body = _maybe_remat(mamba_body, remat)
        if fam == "ssm" or not cfg.attn_every:
            x, _ = jax.lax.scan(mamba_body, x, params["layers"])
        else:
            # zamba2: scan segments of mamba layers, shared attn in between
            L = cfg.n_layers
            every = cfg.attn_every
            start = 0
            while start < L:
                seg = min(every, L - start)
                seg_params = jax.tree_util.tree_map(
                    lambda p: p[start : start + seg], params["layers"])
                x, _ = jax.lax.scan(mamba_body, x, seg_params)
                start += seg
                # the shared attention block closes every mamba segment
                x, _ = B.attn_layer_train(
                    params["shared_attn"], x, cfg=cfg, positions=positions,
                    window=None, moe=False, chunk=attn_chunk, service=service)
    elif fam == "audio":
        enc = batch["enc_embed"].astype(cfg.dtype)
        enc_pos = jnp.broadcast_to(
            jnp.arange(enc.shape[1])[None, :], enc.shape[:2])

        def enc_body(h, p_l):
            h, _ = B.attn_layer_train(p_l, h, cfg=cfg, positions=enc_pos,
                                      window=None, moe=False, causal=False,
                                      chunk=attn_chunk)
            return _cst(h, act_spec), None

        enc_body = _maybe_remat(enc_body, remat)
        enc, _ = jax.lax.scan(enc_body, enc, params["enc_layers"])

        def dec_body(x, p_l):
            hd = cfg.hd
            Be, Se = enc.shape[:2]
            ek = (enc @ p_l["xk"]).reshape(Be, Se, cfg.n_kv_heads, hd).astype(cfg.dtype)
            ev = (enc @ p_l["xv"]).reshape(Be, Se, cfg.n_kv_heads, hd).astype(cfg.dtype)
            x = B.cross_layer_train(p_l, x, {"k": ek, "v": ev}, cfg=cfg,
                                    positions=positions)
            return _cst(x, act_spec), None

        dec_body = _maybe_remat(dec_body, remat)
        x, _ = jax.lax.scan(dec_body, x, params["dec_layers"])
    else:
        raise ValueError(fam)

    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    if cfg.tie_embeddings:
        logits = service_matmul(x, params["embed"].T.astype(cfg.dtype), service)
    else:
        logits = service_matmul(x, params["unembed"], service)
    logits = _cst(logits, logits_spec)
    return logits.astype(jnp.float32), aux, kv


def loss_fn(params: dict, batch: dict, cfg: ArchConfig, **fw_kw):
    logits, aux = forward(params, batch, cfg, **fw_kw)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    total = loss + MOE_AUX_COEF * aux
    return total, {"loss": loss, "aux": aux}


# ---------------------------------------------------------------------------
# decode (serve_step): one new token against a filled cache
# ---------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=None) -> dict:
    """Stacked per-layer cache pytree (leading L axis per stage).

    Uniform-sliding-window archs get a ring buffer of window size instead
    of max_len — O(window) cache for the 500k decode cell."""
    dtype = dtype or cfg.dtype
    hd = cfg.hd
    alloc = cache_len(cfg, max_len)

    def kv(n):
        return {
            "k": jnp.zeros((n, batch, alloc, cfg.n_kv_heads, hd), dtype),
            "v": jnp.zeros((n, batch, alloc, cfg.n_kv_heads, hd), dtype),
        }

    fam = cfg.family
    if fam in ("dense", "vlm"):
        return {"layers": kv(cfg.n_layers)}
    if fam == "moe":
        n_moe = cfg.n_layers - cfg.first_dense_layers
        out = {}
        if cfg.attn_type == "mla":
            out["layers"] = jax.vmap(
                lambda _: init_mla_cache(cfg, batch, max_len, dtype))(jnp.arange(n_moe))
            if cfg.first_dense_layers:
                out["dense0"] = init_mla_cache(cfg, batch, max_len, dtype)
        else:
            out["layers"] = kv(n_moe)
            if cfg.first_dense_layers:
                out["dense0"] = jax.tree_util.tree_map(lambda a: a[0], kv(1))
        return out
    if fam == "ssm":
        return {"layers": jax.vmap(
            lambda _: init_ssm_cache(cfg, batch))(jnp.arange(cfg.n_layers))}
    if fam == "hybrid":
        n_sites = int(np.ceil(cfg.n_layers / cfg.attn_every)) if cfg.attn_every else 0
        return {
            "layers": jax.vmap(
                lambda _: init_ssm_cache(cfg, batch))(jnp.arange(cfg.n_layers)),
            "shared_attn": kv(max(n_sites, 1)),
        }
    if fam == "audio":
        return {
            "dec_layers": kv(cfg.n_layers),
            # cross K/V filled once at prefill from the encoder output
            "cross": {
                "k": jnp.zeros((cfg.n_layers, batch, cfg.encoder_len,
                                cfg.n_kv_heads, hd), dtype),
                "v": jnp.zeros((cfg.n_layers, batch, cfg.encoder_len,
                                cfg.n_kv_heads, hd), dtype),
            },
        }
    raise ValueError(fam)


def cache_len(cfg: ArchConfig, max_len: int) -> int:
    """Positions :func:`init_cache` allocates a sequence: ``max_len``, or a
    uniform sliding window's ring when that is shorter."""
    if cfg.sliding_window and not cfg.local_global_ratio:
        return min(max_len, cfg.sliding_window)
    return max_len


def decode_step(params: dict, cache: dict, token: jnp.ndarray, pos,
                cfg: ArchConfig, *, mla_absorb: bool = True, service=None):
    """token: (B, 1) int32; pos: scalar, or (B,) per-sequence positions for
    the GQA families (continuous batching). Returns (logits (B, V), new
    cache). ``service`` routes the decode-path matmul call sites (attention
    output projection, unembed) and — where the arch's window schedule is
    statically empty — single-token attention through tuned dispatch
    variants."""
    x = params["embed"][token].astype(cfg.dtype)
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.dtype)
    fam = cfg.family
    new_cache = dict(cache)

    if fam in ("dense", "vlm", "moe"):
        windows = jnp.asarray(B.layer_windows(cfg)[cfg.first_dense_layers:])
        moe = fam == "moe"
        if "dense0" in params:
            x, c0, _ = B.attn_layer_decode(params["dense0"], x, cache["dense0"],
                                           pos, cfg=cfg, window=None, moe=False,
                                           mla_absorb=mla_absorb, service=service)
            new_cache["dense0"] = c0

        layer_params, layers, whole = _experts_apart(params["layers"], moe)

        def body(x, xs):
            p_l, c_l, w_l, i = xs
            x, c_l, _ = B.attn_layer_decode(whole(p_l), x, c_l, pos, cfg=cfg,
                                            window=w_l, moe=moe,
                                            mla_absorb=mla_absorb,
                                            service=service, layer=i)
            return x, c_l

        x, cs = jax.lax.scan(body, x, (layer_params, cache["layers"], windows, layers))
        new_cache["layers"] = cs
    elif fam == "ssm":
        def body(x, xs):
            p_l, c_l = xs
            x, c_l = B.mamba_layer_decode(p_l, x, c_l, cfg=cfg)
            return x, c_l

        x, cs = jax.lax.scan(body, x, (params["layers"], cache["layers"]))
        new_cache["layers"] = cs
    elif fam == "hybrid":
        L, every = cfg.n_layers, cfg.attn_every
        site = 0
        layer_caches, attn_caches = [], []
        start = 0
        while start < L:
            seg = min(every, L - start)
            seg_p = jax.tree_util.tree_map(lambda p: p[start:start + seg],
                                           params["layers"])
            seg_c = jax.tree_util.tree_map(lambda c: c[start:start + seg],
                                           cache["layers"])

            def body(x, xs):
                p_l, c_l = xs
                x, c_l = B.mamba_layer_decode(p_l, x, c_l, cfg=cfg)
                return x, c_l

            x, cs = jax.lax.scan(body, x, (seg_p, seg_c))
            layer_caches.append(cs)
            start += seg
            ac = jax.tree_util.tree_map(lambda c: c[site], cache["shared_attn"])
            x, ac, _ = B.attn_layer_decode(params["shared_attn"], x, ac, pos,
                                           cfg=cfg, window=None, moe=False,
                                           service=service)
            attn_caches.append(ac)
            site += 1
        new_cache["layers"] = jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, 0), *layer_caches)
        new_cache["shared_attn"] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs, 0), *attn_caches)
    elif fam == "audio":
        def body(x, xs):
            p_l, c_l, xk, xv = xs
            x, c_l = B.cross_layer_decode(p_l, x, c_l, {"k": xk, "v": xv}, pos,
                                          cfg=cfg)
            return x, c_l

        x, cs = jax.lax.scan(body, x, (params["dec_layers"], cache["dec_layers"],
                                       cache["cross"]["k"], cache["cross"]["v"]))
        new_cache["dec_layers"] = cs
    else:
        raise ValueError(fam)

    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    if cfg.tie_embeddings:
        logits = service_matmul(x, params["embed"].T.astype(cfg.dtype), service)
    else:
        logits = service_matmul(x, params["unembed"], service)
    return logits[:, 0, :].astype(jnp.float32), new_cache
