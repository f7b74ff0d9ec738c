"""Serving steps: prefill (forward over the prompt) + batched greedy decode.

``decode_step`` (one token against a filled cache) lives in
repro.models.model; this module adds the request-batch driver used by the
serving example and benchmarks."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.common import ArchConfig
from repro.models.model import decode_step, forward, init_cache
from repro.obs.metrics import get_registry
from repro.obs.trace import install_jax_hooks, span

__all__ = ["prefill", "greedy_decode", "make_serve_step"]


def prefill(params, batch, cfg: ArchConfig, max_len: int, service=None, **fw_kw):
    """Run the prompt through the model, then replay it through decode_step to
    fill the cache (simple, correct reference path; the fused K/V write is
    ROADMAP Speed 4). ``service`` routes the prompt forward's attention
    (tuned flash ``bq``/``bk``) and matmul call sites through
    :mod:`repro.dispatch` — this is where serving traffic finally meets the
    tuning store.

    Forward, ``init_cache`` and the replay are one jitted program, traced and
    compiled once per (config, ``max_len``, ``fw_kw``, batch, prompt length);
    every later call of that shape runs the cached executable. With a
    ``service`` the program lives in the service's executable cache
    (``jit_cached``), so :meth:`~repro.dispatch.DispatchService.invalidate`
    (a tuned-config hot swap) makes the next call retrace against the new
    configs. ``fw_kw`` are ``forward``'s static keywords (hashable, part of
    the cache key); arrays such as ``enc_embed`` travel in ``batch``. A
    server fed arbitrary prompt lengths compiles once per distinct length
    (bucketing lengths is PERF.md Open question 6).

    Spans (host time; device work is timed to its enqueue): ``serve.prefill``
    (``batch``, ``prompt_len``) around every call. ``serve.prefill.forward``
    and ``serve.prefill.replay`` sit inside the traced body, so they are
    recorded only when JAX traces it (a new shape, or after an
    ``invalidate``); JAX's traces, lowerings and compiles land under
    ``serve.prefill`` as ``jax.*`` child spans. Counters in the
    ``repro.obs`` registry: ``serve_prefill_calls_total`` on every call,
    ``serve_prefill_traces_total`` on every trace."""
    install_jax_hooks()
    get_registry().add("serve_prefill_calls_total")
    if service is not None:
        # the key holds everything the program closes over
        kw = ",".join(f"{k}={v!r}" for k, v in sorted(fw_kw.items()))
        run = service.jit_cached(
            f"prefill/{cfg!r}/max_len={max_len}/{kw}",
            _prefill_program(cfg, max_len, service, fw_kw),
            span="serve.prefill", span_attrs=_prefill_attrs)
        return run(params, batch)
    with span("serve.prefill", **_prefill_attrs(params, batch)):
        return _prefill_jit(params, batch, cfg, max_len, tuple(sorted(fw_kw.items())))


def _prefill_attrs(params, batch) -> dict:
    B, S = batch["tokens"].shape
    return {"batch": B, "prompt_len": S}


def _prefill_program(cfg: ArchConfig, max_len: int, service, fw_kw: dict):
    """The traced body of :func:`prefill`, closed over everything static
    (named ``prefill``: the module's name in a device trace)."""

    def prefill(params, batch):
        get_registry().add("serve_prefill_traces_total")
        B, S = batch["tokens"].shape
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

    return prefill


@functools.partial(jax.jit, static_argnames=("cfg", "max_len", "fw_kw"))
def _prefill_jit(params, batch, cfg: ArchConfig, max_len: int, fw_kw: tuple):
    return _prefill_program(cfg, max_len, None, dict(fw_kw))(params, batch)


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
