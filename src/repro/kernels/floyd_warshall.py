"""Blocked Floyd-Warshall in the (min, +) semiring, Sec. 4.6.

The paper's most instructive case: Polly's static heuristic *regressed* FW by
9x (its ISL schedule destroyed spatial locality), and tiling FW at all
requires `-polly-pragma-ignore-depcheck` because the legality of the blocked
schedule rests on min-plus algebra, which no dependence test can prove.

TPU adaptation: the blocked FW is the classic 3-phase algorithm where every
phase is a **min-plus matrix product** — pure VPU work (no MXU for `min`), so
the kernel's roofline is memory-bound; blocking exists to keep D tiles in
VMEM across the k-sweep exactly as CPU blocking keeps them in cache.

  phase 1  diagonal block transitive closure (in-block FW),
  phase 2  row panel  D[kb,j] = min(D[kb,j], D[kb,kb] (x) D[kb,j]),
           col panel  D[i,kb] = min(D[i,kb], D[i,kb] (x) D[kb,kb]),
  phase 3  trailing   D[i,j]  = min(D[i,j],  D[i,kb] (x) D[kb,j])   [Pallas]

``allow_semiring_reassociation=True`` is mandatory to run the blocked kernel
— the explicit, caller-visible analog of ``-polly-pragma-ignore-depcheck``.
Knobs: ``bs`` (block), ``bi``/``bj`` (phase-3 grid tiles), ``unroll`` (the
k-sweep unroll factor inside the kernel, the paper's unroll-pragma analog;
the sweep steps in chunks of 8 k, and ``unroll`` chunks share one loop
iteration).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.util import cdiv, default_interpret, pad_to

__all__ = ["floyd_warshall", "minplus_update"]

_BIG = 1.0e18  # padding distance: +inf surrogate that survives addition


# k-steps per loop chunk: one sublane tile of the transposed A panel
_KC = 8


def _minplus_kernel(d_ref, a_ref, b_ref, o_ref, at_ref, *, bs: int, unroll: int):
    """o = min(d, min_k a[:, k] + b[k, :]) over the bs-wide contraction.

    Column k of the A block sits at a dynamic lane offset, which Mosaic
    cannot load. The block is transposed once into VMEM so that k indexes
    rows; each 8-row chunk is transposed back in registers, giving its 8
    columns at static lane offsets."""
    at_ref[...] = a_ref[...].T

    def body(c, acc):
        k0 = pl.multiple_of(c * _KC, _KC)
        cols = at_ref[pl.ds(k0, _KC), :].T          # (bi, KC)
        rows = b_ref[pl.ds(k0, _KC), :]             # (KC, bj)
        for r in range(_KC):
            acc = jnp.minimum(acc, cols[:, r:r + 1] + rows[r:r + 1, :])
        return acc

    o_ref[...] = jax.lax.fori_loop(0, bs // _KC, body, d_ref[...],
                                   unroll=unroll)


def minplus_update(
    D: jnp.ndarray,
    A: jnp.ndarray,
    B: jnp.ndarray,
    *,
    bi: int = 128,
    bj: int = 128,
    unroll: int = 1,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """min(D, A (x) B): D (n x m), A (n x bs), B (bs x m); one k-block deep."""
    if interpret is None:
        interpret = default_interpret()
    n, m = D.shape
    bs = A.shape[1]
    assert A.shape == (n, bs) and B.shape == (bs, m)
    bi = min(bi, n)
    bj = min(bj, m)

    # the contraction pads to whole chunks: a padded k pairs _BIG with
    # _BIG, which never undercuts a real distance
    Dp = pad_to(D, (bi, bj), value=_BIG)
    Ap = pad_to(A, (bi, _KC), value=_BIG)
    Bp = pad_to(B, (_KC, bj), value=_BIG)
    ni, nj = Dp.shape[0] // bi, Dp.shape[1] // bj
    bs = Ap.shape[1]

    out = pl.pallas_call(
        functools.partial(_minplus_kernel, bs=bs, unroll=unroll),
        grid=(ni, nj),
        in_specs=[
            pl.BlockSpec((bi, bj), lambda i, j: (i, j)),
            pl.BlockSpec((bi, bs), lambda i, j: (i, 0)),
            pl.BlockSpec((bs, bj), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(Dp.shape, D.dtype),
        scratch_shapes=[pltpu.VMEM((bs, bi), A.dtype)],   # A block, transposed
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        name="floyd_warshall",
        interpret=interpret,
    )(Dp, Ap, Bp)
    return out[:n, :m]


def _closure_in_block(D: jnp.ndarray) -> jnp.ndarray:
    """In-block Floyd-Warshall (phase 1), bs relaxation sweeps."""
    bs = D.shape[0]

    def step(k, M):
        return jnp.minimum(M, M[:, k][:, None] + M[k, :][None, :])

    return jax.lax.fori_loop(0, bs, step, D)


def floyd_warshall(
    path: jnp.ndarray,
    *,
    bs: int = 64,
    bi: int = 128,
    bj: int = 128,
    unroll: int = 1,
    allow_semiring_reassociation: bool = False,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """All-pairs shortest paths. The blocked schedule reorders (min, +)
    reductions, which is only legal because (min, +) is a commutative
    semiring; like Polly, we refuse unless the caller asserts it."""
    if not allow_semiring_reassociation:
        raise ValueError(
            "blocked Floyd-Warshall reassociates the (min,+) reduction; pass "
            "allow_semiring_reassociation=True (the -polly-pragma-ignore-"
            "depcheck analog) or use ref.floyd_warshall_ref"
        )
    if interpret is None:
        interpret = default_interpret()
    N = path.shape[0]
    bs = min(bs, N)
    Dp = pad_to(path, (bs, bs), value=_BIG)
    Np = Dp.shape[0]
    nb = Np // bs

    def block_round(kb, D):
        off = kb * bs
        # phase 1: diagonal block closure
        diag = jax.lax.dynamic_slice(D, (off, off), (bs, bs))
        diag = _closure_in_block(diag)
        D = jax.lax.dynamic_update_slice(D, diag, (off, off))

        # phase 2: row panel then column panel (each one min-plus product)
        row = jax.lax.dynamic_slice(D, (off, 0), (bs, Np))
        row = minplus_update(row, diag, row, bi=bs, bj=bj, unroll=unroll,
                             interpret=interpret)
        D = jax.lax.dynamic_update_slice(D, row, (off, 0))

        col = jax.lax.dynamic_slice(D, (0, off), (Np, bs))
        col = minplus_update(col, col, diag, bi=bi, bj=bs, unroll=unroll,
                             interpret=interpret)
        D = jax.lax.dynamic_update_slice(D, col, (0, off))

        # phase 3: trailing full update (the Pallas grid kernel)
        D = minplus_update(D, col, row, bi=bi, bj=bj, unroll=unroll,
                           interpret=interpret)
        return D

    out = jax.lax.fori_loop(0, nb, block_round, Dp)
    return out[:N, :N]
