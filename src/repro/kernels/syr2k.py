"""syr2k Pallas kernel: C = alpha*A@B^T + alpha*B@A^T + beta*C (Sec. 4.1).

This is the paper's flagship case study. Knob mapping:

  * P3/P4/P5 tile sizes -> ``bi``/``bj``/``bk`` (C-row block, C-col block,
    contraction block over M);
  * P2 interchange      -> ``interchange`` (swap which of the two C block axes
    is the outer grid loop);
  * P0/P1 array packing -> ``pack_a``/``pack_b``: stage the A (resp. B) tiles
    through an explicit VMEM scratch copy before the MXU ops — the local-
    buffer copy Polly's ``pack array`` performs. The accompanying space
    (spaces.py) reproduces the paper's InCondition: pack_b requires pack_a.

A and B are both consumed under two different index maps (row-block i and
row-block j) because C_ij needs A_i B_j^T + B_i A_j^T.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.util import cdiv, default_interpret, pad_to, unpad

__all__ = ["syr2k"]


def _syr2k_kernel(
    c_ref, ai_ref, bj_ref, bi_ref, aj_ref, o_ref, acc_ref, pa_ref, pb_ref,
    *, nk: int, alpha: float, beta: float, pack_a: bool, pack_b: bool,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = beta * c_ref[...].astype(jnp.float32)

    ai = ai_ref[...]
    aj = aj_ref[...]
    bi = bi_ref[...]
    bj = bj_ref[...]
    if pack_a:  # stage A tiles in a dedicated VMEM buffer (packing)
        pa_ref[...] = ai
        ai = pa_ref[...]
    if pack_b:
        pb_ref[...] = bi
        bi = pb_ref[...]

    acc_ref[...] += alpha * jnp.dot(ai, bj.T, preferred_element_type=jnp.float32)
    acc_ref[...] += alpha * jnp.dot(bi, aj.T, preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def syr2k(
    C: jnp.ndarray,
    A: jnp.ndarray,
    B: jnp.ndarray,
    alpha: float = 1.5,
    beta: float = 1.2,
    *,
    bi: int = 128,
    bj: int = 128,
    bk: int = 128,
    interchange: bool = False,
    pack_a: bool = False,
    pack_b: bool = False,
    interpret: bool | None = None,
) -> jnp.ndarray:
    if interpret is None:
        interpret = default_interpret()
    N, M = A.shape
    assert B.shape == (N, M) and C.shape == (N, N)

    bi = min(bi, N)
    bj = min(bj, N)
    bk = min(bk, M)

    # N must pad to a common multiple of bi and bj (both tile the same axis)
    import math

    l = math.lcm(bi, bj)
    Np = cdiv(N, l) * l
    Ap = pad_to(A, (Np, bk))
    Bp = pad_to(B, (Np, bk))
    Cp = pad_to(C, (Np, Np))

    ni, nj, nk = Np // bi, Np // bj, cdiv(M, bk)

    if interchange:
        grid = (nj, ni, nk)
        gi = lambda j, i, k: i
        gj = lambda j, i, k: j
        gk = lambda j, i, k: k
    else:
        grid = (ni, nj, nk)
        gi = lambda i, j, k: i
        gj = lambda i, j, k: j
        gk = lambda i, j, k: k

    out = pl.pallas_call(
        functools.partial(
            _syr2k_kernel, nk=nk, alpha=alpha, beta=beta,
            pack_a=pack_a, pack_b=pack_b,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bi, bj), lambda *g: (gi(*g), gj(*g))),   # C
            pl.BlockSpec((bi, bk), lambda *g: (gi(*g), gk(*g))),   # A_i
            pl.BlockSpec((bj, bk), lambda *g: (gj(*g), gk(*g))),   # B_j
            pl.BlockSpec((bi, bk), lambda *g: (gi(*g), gk(*g))),   # B_i
            pl.BlockSpec((bj, bk), lambda *g: (gj(*g), gk(*g))),   # A_j
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda *g: (gi(*g), gj(*g))),
        out_shape=jax.ShapeDtypeStruct((Cp.shape[0], Cp.shape[1]), C.dtype),
        scratch_shapes=[
            pltpu.VMEM((bi, bj), jnp.float32),  # accumulator
            pltpu.VMEM((bi, bk), A.dtype),      # packed A tile
            pltpu.VMEM((bi, bk), B.dtype),      # packed B tile
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name="syr2k",
        interpret=interpret,
    )(Cp, Ap, Bp, Bp, Ap)
    return unpad(out, (N, N))
