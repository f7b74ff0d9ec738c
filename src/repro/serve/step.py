"""Serving steps: prefill (forward over the prompt) + batched greedy decode.

``decode_step`` (one token against a filled cache) lives in
repro.models.model; this module adds the request-batch driver used by the
serving example and benchmarks."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import ArchConfig
from repro.models.model import decode_step, forward, init_cache
from repro.obs.trace import install_jax_hooks, span

__all__ = ["prefill", "greedy_decode", "make_serve_step"]


def prefill(params, batch, cfg: ArchConfig, max_len: int, service=None, **fw_kw):
    """Run the prompt through the model, then replay it through decode_step to
    fill the cache (simple, correct reference path; a fused prefill-with-cache
    is a §Perf optimization). ``service`` routes the prompt forward's
    attention (tuned flash ``bq``/``bk``) and matmul call sites through
    :mod:`repro.dispatch` — this is where serving traffic finally meets the
    tuning store.

    Spans (host time; device work is timed to its enqueue): ``serve.prefill``
    around the call, ``serve.prefill.forward`` and ``serve.prefill.replay``
    around its two halves. JAX's traces, lowerings and compiles land inside
    them as ``jax.*`` child spans."""
    install_jax_hooks()
    B, S = batch["tokens"].shape
    with span("serve.prefill", batch=B, prompt_len=S):
        with span("serve.prefill.forward"):
            logits, _ = forward(params, batch, cfg, service=service, **fw_kw)
        cache = init_cache(cfg, B, max_len)

        def body(cache, t):
            _, cache = decode_step(params, cache, jax.lax.dynamic_slice_in_dim(
                batch["tokens"], t, 1, axis=1), t, cfg, service=service)
            return cache, None

        with span("serve.prefill.replay"):
            cache, _ = jax.lax.scan(body, cache, jnp.arange(S))
    return logits, cache


def make_serve_step(cfg: ArchConfig, *, mla_absorb: bool = True, service=None):
    """serve_step(params, cache, token, pos) -> (next_token, logits, cache).

    With a :class:`repro.dispatch.DispatchService`, the step is routed
    through the service's compiled-executable cache — every caller asking for
    the same model config shares one jitted entry point — and the decode
    matmul call sites inside resolve tuned block shapes from the service's
    store, so its hit/miss counters cover serving traffic alongside kernel
    dispatches. Each call through the service's proxy is a ``serve.step``
    span (attribute ``batch``): the host time to launch one step."""

    def serve_step(params, cache, token, pos):
        logits, cache = decode_step(params, cache, token, pos, cfg,
                                    mla_absorb=mla_absorb, service=service)
        nxt = jnp.argmax(logits, axis=-1).astype(token.dtype)[:, None]
        return nxt, logits, cache

    if service is not None:
        # key on the full dataclass repr: two configs sharing a name (e.g. a
        # full model and its reduced() variant) must not share a closure
        return service.jit_cached(
            f"serve_step/{cfg!r}/absorb={mla_absorb}", serve_step,
            span="serve.step", span_attrs=_step_attrs)
    return serve_step


def _step_attrs(params, cache, token, pos) -> dict:
    return {"batch": token.shape[0]}


def greedy_decode(params, cfg: ArchConfig, prompt: jnp.ndarray, steps: int,
                  max_len: int, service=None, **fw_kw):
    """prompt: (B, S). Returns (B, steps) generated ids. ``service`` routes
    prefill attention and the per-step matmuls through tuned dispatch
    variants and the decode step through the service's executable cache."""
    batch = {"tokens": prompt}
    if cfg.family == "audio":
        batch["enc_embed"] = fw_kw.pop("enc_embed")
    logits, cache = prefill(params, batch, cfg, max_len, service=service, **fw_kw)
    B, S = prompt.shape
    tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(prompt.dtype)[:, None]
    serve = make_serve_step(cfg, service=service)

    def body(carry, t):
        tok, cache = carry
        nxt, _, cache = serve(params, cache, tok, t)
        return (nxt, cache), tok[:, 0]

    (_, _), toks = jax.lax.scan(body, (tok, cache), S + jnp.arange(steps))
    return toks.T
