"""Tunable tiled matmul Pallas kernel — the MXU-facing building block.

The paper's three pragma families map onto this kernel's knobs:

  * tiling       -> ``bm``/``bn``/``bk`` BlockSpec block shapes (VMEM tiles);
  * interchange  -> grid dimension order (``interchange=True`` makes the
                    N-block loop outer / M-block inner, changing which operand
                    tile stays resident across consecutive grid steps). The
                    contraction dimension stays innermost *by construction* so
                    every point of the space is a legal schedule;
  * array packing-> ``pack=True`` accumulates in an explicit f32 VMEM scratch
                    buffer and writes HBM once (the pack-into-local-buffer
                    analog); ``pack=False`` read-modify-writes the output
                    block in its own dtype each K step.

``interpret=True`` (the CPU default) runs the kernel body in Python for
correctness validation against ``ref.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.util import cdiv, default_interpret, pad_to, unpad

__all__ = ["tiled_matmul"]


def _mm_kernel_pack(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _mm_kernel_nopack(a_ref, b_ref, o_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    ).astype(o_ref.dtype)


def tiled_matmul(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interchange: bool = False,
    pack: bool = True,
    out_dtype=None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """C = A @ B with explicit VMEM tiling. Shapes need not be multiples of
    the block sizes (zero padding is applied and stripped)."""
    if interpret is None:
        interpret = default_interpret()
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype

    bm = min(bm, max(M, 1))
    bn = min(bn, max(N, 1))
    bk = min(bk, max(K, 1))

    ap = pad_to(a, (bm, bk))
    bp = pad_to(b, (bk, bn))
    mi, nj, kk = cdiv(M, bm), cdiv(N, bn), cdiv(K, bk)

    if interchange:
        grid = (nj, mi, kk)
        a_map = lambda j, i, k: (i, k)
        b_map = lambda j, i, k: (k, j)
        o_map = lambda j, i, k: (i, j)
    else:
        grid = (mi, nj, kk)
        a_map = lambda i, j, k: (i, k)
        b_map = lambda i, j, k: (k, j)
        o_map = lambda i, j, k: (i, j)

    common = dict(
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), a_map),
            pl.BlockSpec((bk, bn), b_map),
        ],
        out_specs=pl.BlockSpec((bm, bn), o_map),
        out_shape=jax.ShapeDtypeStruct((ap.shape[0], bp.shape[1]), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="matmul",
    )
    if pack:
        out = pl.pallas_call(
            functools.partial(_mm_kernel_pack, nk=kk),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            **common,
        )(ap, bp)
    else:
        out = pl.pallas_call(functools.partial(_mm_kernel_nopack, nk=kk), **common)(ap, bp)
    return unpad(out, (M, N))
