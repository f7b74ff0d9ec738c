"""Decode flash attention Pallas kernel — the serving latency hot path.

Prefill rides :mod:`repro.kernels.flash_attention`; decode until now rode the
dense einsum in ``models.attention.gqa_decode``, which materializes the full
(B, K, G, S) score tensor every token. This kernel streams the KV cache
through VMEM in ``bk``-sized blocks with the online-softmax recurrence, so
per-token HBM traffic is exactly q + k + v + o and the score block never
leaves VMEM.

The mask reproduces ``gqa_decode``'s ring/window semantics exactly (the
property tests pin bit-closeness): slot ``j`` of a ring cache of length S
holds absolute position ``cur_pos - ((cur_pos - j) mod S)``; positions
beyond ``cur_pos``, negative (not yet written), or older than the sliding
window are masked. ``cur_pos`` is *per row* — a (BH,) int32 vector — because
continuous batching gives every sequence in the batch its own decode
position; it rides into the kernel as a scalar-prefetch operand
(``PrefetchScalarGridSpec``), available in SMEM before the grid body runs.

Schedule knobs (the paper's pragma vocabulary, decode edition):

  * ``bk`` — KV block length (VMEM tile of the cache stream);
  * ``hg`` — head grouping: how many (batch*kv-head) rows share one grid
    cell, amortizing grid overhead when G*hd is far below the MXU tile;
  * ``impl`` — Pallas kernel vs the chunked-XLA fallback (host backend).

The paged KV cache's ``page_size`` is a fourth axis of the same tuned space,
realized by the cache layout (``serve.kvcache``) rather than this kernel:
it decides the seq-bucket granularity the dispatch signature sees.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.util import cdiv, default_interpret, pad_to

__all__ = ["decode_attention", "chunked_decode_xla", "decode_ref"]

_NEG = -1.0e30


def _decode_mask(slots, cp, *, s_real: int, ring: bool, window: int):
    """The allow-mask shared by every impl (and the dense reference).

    ``slots``: int32 cache-slot indices, any shape broadcastable with ``cp``;
    ``cp``: per-row current positions. Returns (kpos, valid)."""
    if ring:
        kpos = cp - jnp.mod(cp - slots, s_real)
    else:
        kpos = jnp.broadcast_to(slots, jnp.broadcast_shapes(slots.shape, cp.shape))
    valid = (slots < s_real) & (kpos >= 0) & (kpos <= cp)
    if window > 0:
        valid &= (cp - kpos) < window
    return kpos, valid


def _decode_kernel(cp_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, nk: int, bk: int, hg: int, scale: float, s_real: int,
                   ring: bool, window: int):
    i, kb = pl.program_id(0), pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(jnp.float32)          # (hg, G, hd)
    k = k_ref[...].astype(jnp.float32)          # (hg, bk, hd)
    v = v_ref[...].astype(jnp.float32)          # (hg, bk, hd)

    # (hg, G, hd) x (hg, bk, hd) -> (hg, G, bk), batched over the row axis
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale

    hgG = (hg, q.shape[1], bk)
    slots = kb * bk + jax.lax.broadcasted_iota(jnp.int32, hgG, 2)
    # SMEM holds scalars only: read each row's position as one and place it
    # on that row of the block
    row = jax.lax.broadcasted_iota(jnp.int32, hgG, 0)
    cp = jnp.full(hgG, cp_ref[i * hg], jnp.int32)
    for r in range(1, hg):
        cp = jnp.where(row == r, cp_ref[i * hg + r], cp)
    _, valid = _decode_mask(slots, cp, s_real=s_real, ring=ring, window=window)
    s = jnp.where(valid, s, _NEG)

    m_prev = m_ref[...]                          # (hg, G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    # explicit zeroing (not just the _NEG offset): a fully-masked block —
    # routine under ring/window decode — would otherwise contribute
    # exp(_NEG - _NEG) = 1 per slot to the denominator
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kb == nk - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


def decode_attention(
    q: jnp.ndarray,            # (BH, G, hd) — batch*kv_heads rows, G query heads
    k: jnp.ndarray,            # (BH, S, hd) — cache, S = seq bucket
    v: jnp.ndarray,            # (BH, S, hd)
    cur_pos: jnp.ndarray,      # (BH,) int32 — per-row decode position
    *,
    ring: bool = False,
    window: int = 0,           # static; <=0 disables the sliding window
    bk: int = 128,
    hg: int = 1,
    scale: float | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One-token attention against a filled cache, per-row positions."""
    if interpret is None:
        interpret = default_interpret()
    BH, G, hd = q.shape
    S = k.shape[1]
    scale = scale if scale is not None else hd ** -0.5
    bk = max(1, min(bk, S))
    hg = max(1, min(hg, BH))
    cur_pos = jnp.asarray(cur_pos, jnp.int32).reshape(-1)
    if cur_pos.shape[0] == 1 and BH > 1:
        cur_pos = jnp.broadcast_to(cur_pos, (BH,))

    qp = pad_to(q, (hg, 1, 1))
    kp = pad_to(k, (hg, bk, 1))
    vp = pad_to(v, (hg, bk, 1))
    # padded rows carry cur_pos = -1: every slot fails kpos <= cur_pos, the
    # whole row masks out, and the zero output is sliced away below
    cpp = pad_to(cur_pos, (hg,), value=-1)
    nbh, nk = qp.shape[0] // hg, kp.shape[1] // bk

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nbh, nk),
        in_specs=[
            pl.BlockSpec((hg, G, hd), lambda i, j, cp: (i, 0, 0)),
            pl.BlockSpec((hg, bk, hd), lambda i, j, cp: (i, j, 0)),
            pl.BlockSpec((hg, bk, hd), lambda i, j, cp: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((hg, G, hd), lambda i, j, cp: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hg, G, hd), jnp.float32),   # output accumulator
            pltpu.VMEM((hg, G, 1), jnp.float32),    # running max
            pltpu.VMEM((hg, G, 1), jnp.float32),    # running denominator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, nk=nk, bk=bk, hg=hg, scale=scale,
                          s_real=S, ring=ring, window=int(window or 0)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        name="decode_attention",
        interpret=interpret,
    )(cpp, qp, kp, vp)
    return out[:BH]


def chunked_decode_xla(
    q: jnp.ndarray,            # (BH, G, hd)
    k: jnp.ndarray,            # (BH, S, hd)
    v: jnp.ndarray,            # (BH, S, hd)
    cur_pos: jnp.ndarray,      # (BH,) int32
    *,
    ring: bool = False,
    window: int = 0,
    bk: int = 128,
    scale: float | None = None,
) -> jnp.ndarray:
    """The XLA fallback variant: same contract and same online-softmax
    recurrence, scanned over ``bk``-length cache chunks — interchangeable
    with :func:`decode_attention` under one dispatch entry (host backend,
    where interpret-mode Pallas is orders slower than XLA)."""
    BH, G, hd = q.shape
    S = k.shape[1]
    scale = scale if scale is not None else hd ** -0.5
    bk = max(1, min(bk, S))
    cur_pos = jnp.asarray(cur_pos, jnp.int32).reshape(-1)
    if cur_pos.shape[0] == 1 and BH > 1:
        cur_pos = jnp.broadcast_to(cur_pos, (BH,))

    kp = pad_to(k, (1, bk, 1))
    vp = pad_to(v, (1, bk, 1))
    nk = kp.shape[1] // bk
    kc = kp.reshape(BH, nk, bk, hd).transpose(1, 0, 2, 3)   # (nk, BH, bk, hd)
    vc = vp.reshape(BH, nk, bk, hd).transpose(1, 0, 2, 3)

    qf = q.astype(jnp.float32)
    cp = cur_pos.reshape(BH, 1, 1)
    window = int(window or 0)

    def step(carry, blk):
        m_prev, l_prev, acc = carry
        ci, kb, vb = blk
        s = jnp.einsum("bgh,bsh->bgs", qf, kb.astype(jnp.float32)) * scale
        slots = ci * bk + jnp.arange(bk, dtype=jnp.int32).reshape(1, 1, bk)
        _, valid = _decode_mask(slots, cp, s_real=S, ring=ring, window=window)
        s = jnp.where(valid, s, _NEG)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bgs,bsh->bgh", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc), None

    init = (jnp.full((BH, G, 1), _NEG, jnp.float32),
            jnp.zeros((BH, G, 1), jnp.float32),
            jnp.zeros((BH, G, hd), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(
        step, init, (jnp.arange(nk, dtype=jnp.int32), kc, vc))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def decode_ref(q, k, v, cur_pos, *, ring=False, window=0, scale=None):
    """Dense reference in the kernel's own (BH, G, hd) layout — the oracle
    the property tests compare both impls against (mirrors
    ``models.attention.gqa_decode`` slot math exactly)."""
    BH, G, hd = q.shape
    S = k.shape[1]
    scale = scale if scale is not None else hd ** -0.5
    cur_pos = jnp.asarray(cur_pos, jnp.int32).reshape(-1)
    if cur_pos.shape[0] == 1 and BH > 1:
        cur_pos = jnp.broadcast_to(cur_pos, (BH,))
    s = jnp.einsum("bgh,bsh->bgs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    slots = jnp.arange(S, dtype=jnp.int32).reshape(1, 1, S)
    _, valid = _decode_mask(slots, cur_pos.reshape(BH, 1, 1), s_real=S,
                            ring=ring, window=int(window or 0))
    s = jnp.where(valid, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgs,bsh->bgh", p, v.astype(jnp.float32))
    return o.astype(q.dtype)
