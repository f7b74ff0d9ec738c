"""Flash attention Pallas kernel (beyond-paper §Perf move).

The dry-run walker shows the dense-arch train cells are memory-bound almost
entirely through attention score materialization: the XLA chunked path
round-trips (chunk x S) score tensors through HBM several times per layer
(forward, mask, softmax, backward, remat). This kernel keeps the score block
in VMEM for good: per (batch*head, q-block) grid cell it streams K/V blocks
through VMEM with the online-softmax recurrence, so HBM traffic is exactly
q + k + v + o — independent of S^2.

Schedule knobs (the paper's pragma vocabulary, again):
  * ``bq`` / ``bk``  — query / key block sizes (VMEM tiles);
  * the K-sweep is the innermost grid dim ('arbitrary'), batch*heads and
    q-blocks are 'parallel'.

HBM-traffic napkin math per (B, H, S, hd), used by the §Perf accounting:
    flash:  (3 reads + 1 write) * B*H*S*hd * bytes         ~ O(S)
    xla  :  + 2 * n_passes * B*H*S^2 * bytes(score)        ~ O(S^2)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.util import cdiv, default_interpret, pad_to

__all__ = ["flash_attention", "flash_hbm_bytes", "xla_attention_hbm_bytes"]

_NEG = -1.0e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                  *, nk: int, bq: int, bk: int, scale: float, causal: bool,
                  sk_real: int):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)            # (bq, hd)
    k = k_ref[0].astype(jnp.float32)            # (bk, hd)
    v = v_ref[0].astype(jnp.float32)            # (bk, hd)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (bq, bk)
    kpos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = kpos < sk_real            # padded keys must not contribute
    if causal:
        qb = pl.program_id(1)
        qpos = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        valid &= qpos >= kpos
    s = jnp.where(valid, s, _NEG)

    m_prev = m_ref[...]                          # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                       # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)              # (bq, 1)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kb == nk - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,            # (BH, Sq, hd) — batch*heads flattened
    k: jnp.ndarray,            # (BH, Sk, hd)
    v: jnp.ndarray,            # (BH, Sk, hd)
    *,
    causal: bool = True,
    bq: int = 128,
    bk: int = 128,
    scale: float | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    if interpret is None:
        interpret = default_interpret()
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    scale = scale if scale is not None else hd ** -0.5
    bq = min(bq, Sq)
    bk = min(bk, Sk)

    qp = pad_to(q, (1, bq, 1))
    kp = pad_to(k, (1, bk, 1))
    vp = pad_to(v, (1, bk, 1))
    nq, nk = qp.shape[1] // bq, kp.shape[1] // bk

    out = pl.pallas_call(
        functools.partial(_flash_kernel, nk=nk, bq=bq, bk=bk, scale=scale,
                          causal=causal, sk_real=Sk),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),   # output accumulator
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running denominator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name="flash_attention",
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :Sq, :]


# ---------------------------------------------------------------------------
# analytic HBM accounting (used by §Perf to adjust the walker's memory term)
# ---------------------------------------------------------------------------


def flash_hbm_bytes(B: int, H: int, Kh: int, Sq: int, Sk: int, hd: int,
                    dtype_bytes: int = 2, bq: int = 128) -> float:
    """q read + (k, v) streamed once per q-block + o write."""
    nq = cdiv(Sq, bq)
    q_io = 2 * B * H * Sq * hd * dtype_bytes           # read q + write o
    kv_io = nq * 2 * B * Kh * Sk * hd * dtype_bytes    # k+v per q-block sweep
    return float(q_io + kv_io)


def xla_attention_hbm_bytes(B: int, H: int, Sq: int, Sk: int, hd: int,
                            dtype_bytes: int = 4, n_passes: int = 6) -> float:
    """The materializing path: score tensors cross HBM ~n_passes times
    (matmul out, mask, softmax in/out, backward twice)."""
    return float(n_passes * B * H * Sq * Sk * dtype_bytes)
