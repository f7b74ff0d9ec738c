"""The plain references against the system, at small sizes on the CPU, and
the controls that the correctness limits must fail."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench.drivers import serve_closed
from bench.reference import dense_gqa
from bench.tests import rehearsal

SMALL = dict(rehearsal.TINY_CONFIG, hf=dict(rehearsal.TINY_CONFIG["hf"], vocab_size=512),
             program=dict(rehearsal.TINY_CONFIG["program"], vocab_size=512))


def _setup(dtype):
    dims = dense_gqa.Dims.from_config(SMALL)
    cfg = dataclasses.replace(serve_closed.program_config(SMALL), dtype=dtype)
    w = dense_gqa.make_weights(dims, 1234, dtype)
    return dims, cfg, w, serve_closed.to_program(w)


def _ref_logits(w, dims, seq):
    """Full reference logits, by picking every vocabulary id in turn."""
    S = len(seq)
    out = []
    for v in range(dims.vocab):
        _, at, _ = dense_gqa.score(w, jnp.asarray(seq), jnp.full((S,), v, jnp.int32),
                                   dims=dims, block=S)
        out.append(np.asarray(at))
    return np.stack(out, axis=-1)


def test_weights_follow_the_seed():
    dims = dense_gqa.Dims.from_config(SMALL)
    a, b, c = (dense_gqa.make_weights(dims, s, jnp.bfloat16) for s in (5, 5, 6))
    same = jax.tree_util.tree_map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree_util.tree_leaves(same))
    assert not bool((a["layers"]["q"] == c["layers"]["q"]).all())


def test_weights_fit_the_systems_layout():
    from repro.models import abstract_params

    dims, cfg, w, params = _setup(jnp.bfloat16)
    want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), abstract_params(cfg))
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    assert got == want


def test_reference_matches_the_systems_forward_in_float32():
    """Both float32, matrix products at full precision on the CPU: they
    differ by summation order alone, a few float32 ulps of the logits'
    scale (about 4); 1e-4 absolute leaves two orders of margin, while a
    wrong norm, bias or rotary convention moves logits by 0.1 or more."""
    from repro.models import forward

    dims, cfg, w, params = _setup(jnp.float32)
    seq = np.random.default_rng(0).integers(0, dims.vocab, 32).astype(np.int32)
    want, _ = forward(params, {"tokens": jnp.asarray(seq)[None]}, cfg)
    best, at, top = dense_gqa.score(w, jnp.asarray(seq), jnp.zeros(32, jnp.int32),
                                    dims=dims, block=32)
    want = np.asarray(want[0])
    np.testing.assert_allclose(np.asarray(best), want.max(-1), atol=1e-4)
    np.testing.assert_allclose(np.asarray(at), want[:, 0], atol=1e-4)
    assert (np.asarray(top) == want.argmax(-1)).all()


def test_serving_through_the_cache_matches_the_reference():
    """Prefill, then greedy decoding through the paged cache's serve step,
    float32: each step's logits agree with the reference's full forward
    pass over the same tokens (tolerance as above)."""
    from repro.serve import PagedKVCache, make_serve_step, prefill

    dims, cfg, w, params = _setup(jnp.float32)
    prompt = np.random.default_rng(1).integers(0, dims.vocab, 12).astype(np.int32)
    pc = PagedKVCache(cfg, 2, 32, page_size=16)
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompt)[None]}, cfg,
                            max_len=pc.alloc)
    pc.admit(1, cache, len(prompt))
    serve = jax.jit(make_serve_step(cfg))
    toks, got = [int(np.argmax(logits[0, -1]))], [np.asarray(logits[0, -1])]
    for i in range(6):
        view = pc.view([1], 32)
        nxt, lg, view = serve(params, view, jnp.asarray([[toks[-1]]], jnp.int32),
                              jnp.asarray([len(prompt) + i], jnp.int32))
        pc.writeback([1], 32, view)
        pc.advance([1])
        toks.append(int(nxt[0, 0]))
        got.append(np.asarray(lg[0]))
    seq = np.concatenate([prompt, toks[:-1]]).astype(np.int32)
    ref = _ref_logits(w, dims, seq)[len(prompt) - 1:]
    np.testing.assert_allclose(np.stack(got), ref, atol=1e-4)


def _gap_of(w, dims, seq, served_from, quant=None):
    nxt = jnp.asarray(np.concatenate([seq[1:], [0]]).astype(np.int32))
    best, picked, top = dense_gqa.score(w, jnp.asarray(seq), nxt, dims=dims, block=len(seq))
    if quant is None:
        return float(np.max(np.asarray(best - picked)[served_from:-1]))
    _, _, top_c = dense_gqa.score(w, jnp.asarray(seq), nxt, dims=dims, block=len(seq),
                                  quant=quant)
    best, picked_c, _ = dense_gqa.score(w, jnp.asarray(seq), top_c, dims=dims,
                                        block=len(seq))
    return float(np.max(np.asarray(best - picked_c)[served_from:-1]))


def test_fp8_control_reads_wider_gaps_than_bf16_serving():
    """The served model's check separates its two readings at this size:
    greedy tokens from the bf16 system sit within rounding of the float32
    reference's best, the float8 control's first choices do not."""
    from repro.serve import greedy_decode

    dims, cfg, w, params = _setup(jnp.bfloat16)
    rng = np.random.default_rng(2)
    gaps, ctl = [], []
    for _ in range(3):
        prompt = rng.integers(0, dims.vocab, (1, 16)).astype(np.int32)
        out = np.asarray(greedy_decode(params, cfg, jnp.asarray(prompt), 48, 64))[0]
        seq = np.concatenate([prompt[0], out]).astype(np.int32)
        gaps.append(_gap_of(w, dims, seq, 15))
        ctl.append(_gap_of(w, dims, seq, 15, quant="fp8"))
    # the check pools every sampled token into one widest gap
    assert max(ctl) >= 3 * max(gaps)
