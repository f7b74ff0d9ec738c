"""covariance Pallas kernel: data (N x M) -> cov (M x M) (Sec. 4.5).

cov = (data - mean)^T (data - mean) / (N-1) — a centered SYRK. Knobs:

  * ``bi``/``bj``  — output (attribute x attribute) tile;
  * ``bk``         — reduction tile over the N data points;
  * ``fuse_center``— subtract the column means inside the kernel (fusing the
                     PolyBench centering loop into the update loop) vs.
                     centering in a separate XLA pass before the kernel;
  * ``interchange``— swap the two output grid axes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.util import cdiv, default_interpret, pad_to, unpad

__all__ = ["covariance"]


def _cov_kernel(di_ref, dj_ref, mi_ref, mj_ref, o_ref, acc_ref,
                *, nk: int, denom: float, fuse_center: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    di = di_ref[...]  # (bk, bi) slab of data columns i
    dj = dj_ref[...]  # (bk, bj)
    if fuse_center:
        di = di - mi_ref[...]  # (1, bi) broadcast over rows
        dj = dj - mj_ref[...]
    acc_ref[...] += jnp.dot(di.T, dj, preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def covariance(
    data: jnp.ndarray,
    *,
    bi: int = 128,
    bj: int = 128,
    bk: int = 256,
    fuse_center: bool = True,
    interchange: bool = False,
    interpret: bool | None = None,
) -> jnp.ndarray:
    if interpret is None:
        interpret = default_interpret()
    N, M = data.shape
    bi = min(bi, M)
    bj = min(bj, M)
    bk = min(bk, N)

    mean = data.mean(axis=0, keepdims=True)  # (1, M)
    if not fuse_center:
        data = data - mean

    l = math.lcm(bi, bj)
    Mp = cdiv(M, l) * l
    dp = pad_to(data, (bk, Mp))
    # padded rows must not perturb the sums: zero rows are exactly neutral
    # when fuse_center=False; when fusing, padded rows would contribute
    # (0-mean)^2, so zero the mean's effect by masking via a row-validity
    # trick: append mean value rows so (row - mean) == 0.
    if fuse_center and dp.shape[0] != N:
        pad_rows = dp.shape[0] - N
        filler = jnp.broadcast_to(pad_to(mean, (1, Mp)), (pad_rows, Mp))
        dp = dp.at[N:, :].set(filler)
    mp = pad_to(mean, (1, Mp))

    ni, nj, nk = Mp // bi, Mp // bj, cdiv(N, bk)

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
            _cov_kernel, nk=nk, denom=float(N - 1), fuse_center=fuse_center
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bi), lambda *g: (gk(*g), gi(*g))),  # data cols i
            pl.BlockSpec((bk, bj), lambda *g: (gk(*g), gj(*g))),  # data cols j
            pl.BlockSpec((1, bi), lambda *g: (0, gi(*g))),        # means i
            pl.BlockSpec((1, bj), lambda *g: (0, gj(*g))),        # means j
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda *g: (gi(*g), gj(*g))),
        out_shape=jax.ShapeDtypeStruct((Mp, Mp), data.dtype),
        scratch_shapes=[pltpu.VMEM((bi, bj), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name="covariance",
        interpret=interpret,
    )(dp, dp, mp, mp)
    return unpad(out, (M, M))
