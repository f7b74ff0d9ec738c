"""The dropless expert layer, its grouped-matmul kernel, and a reduced
Mixtral served through the normal path against the plain reference."""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.grouped_matmul import (grouped_matmul, grouped_matmul_ref,
                                          grouped_matmul_xla)
from repro.models.moe import init_moe, moe_ffn

D, FF, E, K = 16, 24, 8, 2


def _plain(p, x, top_k):
    """Every expert on every token, weighted by its renormalised top-k gate
    (zero where the token did not route to it)."""
    xt = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    probs = np.asarray(jax.nn.softmax(jnp.asarray(xt @ np.asarray(p["router"], np.float64),
                                                  jnp.float32), axis=-1))
    y = np.zeros_like(xt)
    for t in range(len(xt)):
        top = np.argsort(-probs[t], kind="stable")[:top_k]
        g = probs[t, top] / probs[t, top].sum()
        for e, ge in zip(top, g):
            h = xt[t] @ np.asarray(p["wg"][e], np.float64)
            h = h / (1 + np.exp(-h)) * (xt[t] @ np.asarray(p["wu"][e], np.float64))
            y[t] += ge * (h @ np.asarray(p["wd"][e], np.float64))
    return y.reshape(x.shape)


def _layer(seed=7):
    p = init_moe(jax.random.PRNGKey(seed), D, FF, n_experts=E, n_shared=0,
                 dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 12, D))
    return p, x


def test_dropless_layer_equals_all_experts_computation():
    """float32 both; summation order alone differs (1e-5 of outputs of
    order 1)."""
    p, x = _layer()
    y, aux = moe_ffn(p, x, top_k=K)
    np.testing.assert_allclose(np.asarray(y), _plain(p, x, K), atol=1e-5, rtol=1e-5)
    assert np.isfinite(float(aux))


def test_dropless_layer_keeps_every_token_under_skewed_routing():
    """Every token routes to experts 2 and 5: a capacity of 1.25 * 2 * T / E
    rows an expert would have dropped three quarters of them."""
    p, x = _layer(11)
    x = jnp.abs(x)
    router = -np.ones((D, E), np.float32)
    router[:, 2], router[:, 5] = 1.0, 0.9
    p = dict(p, router=jnp.asarray(router))
    y, _ = moe_ffn(p, x, top_k=K)
    want = _plain(p, x, K)
    assert np.abs(want).min(axis=-1).max() > 0
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("first", [0, E // 2])
def test_half_shares_add_up_to_the_uncut_layer(first):
    """Two chips holding experts [0, E/2) and [E/2, E) each compute their
    share; the shares add up to the whole layer's output (float32, summation
    order alone differs)."""
    p, x = _layer(3)
    whole, _ = moe_ffn(p, x, top_k=K)
    half = lambda f: moe_ffn({k: (v[f:f + E // 2] if k != "router" else v)
                              for k, v in p.items()}, x, top_k=K, first=f)[0]
    mine, other = half(first), half(E // 2 - first)
    np.testing.assert_allclose(np.asarray(mine + other), np.asarray(whole),
                               atol=1e-5, rtol=1e-5)
    assert float(jnp.abs(mine).max()) > 0 and float(jnp.abs(other).max()) > 0


def test_dropless_layer_through_the_dispatched_kernel():
    """With a service the layer reaches ``grouped_matmul`` through
    ``service.dispatch``; the interpreted Pallas kernel and XLA's
    ragged_dot agree in float32."""
    from repro.dispatch import DispatchService

    p, x = _layer(5)
    svc = DispatchService(target="tpu")
    y, _ = moe_ffn(p, x, top_k=K, service=svc)
    np.testing.assert_allclose(np.asarray(y), _plain(p, x, K), atol=1e-5, rtol=1e-5)
    # gate/up (d -> ff) and down (ff -> d): two signatures
    assert [e["config"]["impl"] for e in svc.telemetry()["executables"]
            if e["kernel"] == "grouped_matmul"] == ["pallas", "pallas"]


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("layer", [0, 2])
def test_layer_of_a_stack_equals_the_layer_alone(layer, kernel):
    """Given the expert stacks of 3 layers and a traced layer index, the
    layer computes what it computes on that layer's matrices alone: through
    the dispatched kernel, which takes the stacks whole as 3 x E groups,
    and through ragged_dot, which takes the layer's slice (float32)."""
    from repro.dispatch import DispatchService

    layers = [_layer(20 + i)[0] for i in range(3)]
    _, x = _layer(9)
    p = dict(layers[layer], **{m: jnp.stack([q[m] for q in layers])
                               for m in ("wg", "wu", "wd")})
    svc = DispatchService(target="tpu") if kernel else None
    got = jax.jit(lambda i: moe_ffn(p, x, top_k=K, service=svc, layer=i)[0])(layer)
    np.testing.assert_allclose(np.asarray(got), _plain(layers[layer], x, K),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("sizes", [[10, 0, 7, 20, 0], [0, 0, 0, 0, 0],
                                   [37, 0, 0, 0, 0], [3, 4, 5, 6, 7], [1, 0, 0, 0, 2]])
@pytest.mark.parametrize("tiles", [(16, 32, 32), (32, 64, 48), (128, 512, 1024)])
def test_grouped_matmul_matches_per_group_products(sizes, tiles):
    """Uneven and empty groups, rows past the groups' total left zero; the
    Pallas kernel in interpret mode and the XLA variant, float32 against
    per-group products (accumulation order alone differs)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(sum(sizes)))
    lhs = jax.random.normal(k1, (37, 64))
    rhs = jax.random.normal(k2, (5, 64, 96))
    gs = jnp.asarray(sizes, jnp.int32)
    want = np.asarray(grouped_matmul_ref(lhs, rhs, gs))
    tm, tk, tn = tiles
    got = grouped_matmul(lhs, rhs, gs, tm=tm, tk=tk, tn=tn, interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grouped_matmul_xla(lhs, rhs, gs)), want,
                               atol=1e-4, rtol=1e-5)
    assert not np.asarray(got)[sum(sizes):].any()


# -- reduced Mixtral through the serving path against the plain reference ----


def _tiny_mixtral():
    with open(os.path.join(os.path.dirname(__file__), "..", "bench", "configs",
                           "mixtral-8x7b.json")) as f:
        cfg = json.load(f)
    cfg["hf"].update(hidden_size=64, intermediate_size=96, num_attention_heads=4,
                     num_key_value_heads=2, vocab_size=128)
    cfg["program"].update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                          d_ff=96, moe_d_ff=96, vocab_size=128)
    return cfg


def test_reduced_mixtral_served_matches_the_reference(monkeypatch):
    """Prefill, PagedKVCache and the dispatched serve step on the TPU branch
    (Pallas kernels interpreted), all in float32, against the reference's
    full forward logits at every served position. The two differ by
    summation order alone: 1e-4 absolute on logits of order 1 leaves two
    orders of margin, while a wrong norm epsilon, rotary base, gate
    normalisation or a dropped expert moves logits by 1e-2 or more."""
    from bench.reference import moe_gqa
    from repro.dispatch import DispatchService, service
    from repro.kernels import util
    from repro.models.common import ArchConfig
    from repro.serve import PagedKVCache, make_serve_step, prefill

    monkeypatch.setattr(util, "default_target", lambda: "tpu")
    monkeypatch.setattr(service, "default_target", lambda: "tpu")
    cfg = _tiny_mixtral()
    dims = moe_gqa.Dims.from_config(cfg)
    arch = ArchConfig(**cfg["program"], dtype=jnp.float32)
    assert arch.sliding_window is None and arch.rms_eps == 1e-5
    w = moe_gqa.make_weights(dims, 1234, jnp.float32)
    params = moe_gqa.to_program(w)
    svc = DispatchService()
    P, steps, page = 9, 7, 8
    prompt = jax.random.randint(jax.random.PRNGKey(2), (P,), 0, dims.vocab)
    pc = PagedKVCache(arch, 2, P + steps, page_size=page)
    logits, cache = prefill(params, {"tokens": prompt[None]}, arch, max_len=pc.alloc,
                            service=svc)
    pc.admit(1, cache, P)
    serve = make_serve_step(arch, service=svc)
    got = [np.asarray(logits[0, -1])]
    seq = list(np.asarray(prompt))
    tok = int(np.argmax(got[-1]))
    for _ in range(steps):
        seq.append(tok)
        bucket = pc.seq_bucket([1])
        view = pc.view([1], bucket)
        _, lg, view = serve(params, view, jnp.asarray([[tok]], jnp.int32),
                            jnp.asarray([pc.pos[1] + 1], jnp.int32))
        pc.writeback([1], bucket, view)
        pc.advance([1])
        got.append(np.asarray(lg[0]))
        tok = int(np.argmax(got[-1]))
    assert {e["config"]["impl"] for e in svc.telemetry()["executables"]
            if e["kernel"] == "grouped_matmul"} == {"pallas"}

    S = len(seq)
    toks = jnp.asarray(seq, jnp.int32)
    want = np.stack([np.asarray(moe_gqa.score(w, toks, jnp.full((S,), v, jnp.int32),
                                              dims=dims, block=S)[1])
                     for v in range(dims.vocab)], axis=-1)
    np.testing.assert_allclose(np.stack(got), want[P - 1:], atol=1e-4, rtol=0)
