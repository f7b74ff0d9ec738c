"""Grouped matrix multiplication Pallas kernel — the expert layer's matmul.

``lhs`` rows are sorted by group: the first ``group_sizes[0]`` rows belong
to group 0, the next ``group_sizes[1]`` to group 1, and so on. Row ``r`` of
group ``i`` comes out as ``lhs[r] @ rhs[i]``; rows past ``sum(group_sizes)``
come out zero. This is ``jax.lax.ragged_dot``'s contract, and the
mixture-of-experts layer's: token-expert pairs sorted by expert, one weight
matrix per expert.

The schedule follows the megablox ``gmm`` that ships with JAX
(``jax.experimental.pallas.ops.tpu.megablox``), whose ``make_group_metadata``
this kernel reuses: the grid walks ``(n tiles, active (m tile, group)
pairs, k tiles)``, with each pair's m-tile and group id prefetched into
SMEM. An m-tile that straddles a group boundary is visited once per group,
consecutively, so its output block stays in VMEM and each visit stores
only its own group's rows. Empty groups get no grid step: a decode step
that routes no token to an expert never reads that expert's weights.

Schedule knobs (the tuning space, ``kernels.spaces``):

  * ``tm`` — rows of ``lhs`` per tile (clamped to the padded row count);
  * ``tk`` — contraction tile; ``tn`` — output-column tile. Both must
    divide their dimension or are widened to the whole of it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from repro.kernels.util import cdiv, default_interpret

__all__ = ["grouped_matmul", "grouped_matmul_xla", "grouped_matmul_ref",
           "grouped_tiles"]

_ROWS = 16   # bf16 sublane tile: the least row tile Mosaic lays out whole


def grouped_tiles(m: int, k: int, n: int, tm: int, tk: int, tn: int):
    """The tiles a call at ``(m, k, n)`` runs with: ``tm`` clamped to the
    rows rounded up to the sublane tile; ``tk``/``tn`` clamped to their
    dimension, and widened to the whole dimension where they do not divide
    it. Returns ``(tm, tk, tn, m_padded)``."""
    tm = max(_ROWS, min(tm, cdiv(m, _ROWS) * _ROWS))
    tk, tn = min(tk, k), min(tn, n)
    tk = tk if k % tk == 0 else k
    tn = tn if n % tn == 0 else n
    return tm, tk, tn, cdiv(m, tm) * tm


def _gmm_kernel(group_offsets, group_ids, m_tile_ids, lhs_ref, rhs_ref,
                out_ref, acc_ref, *, tm: int, tn: int, tiles_k: int):
    grid_id, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(lhs_ref[...], rhs_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k_i == tiles_k - 1)
    def _store():
        # only this step's group's rows of the m-tile: the other rows belong
        # to the groups visited just before or after it
        g = group_ids[grid_id]
        row = m_tile_ids[grid_id] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, tn), 0)
        mine = (row >= group_offsets[g]) & (row < group_offsets[g + 1])
        out_ref[...] = jnp.where(mine, acc_ref[...],
                                 out_ref[...].astype(jnp.float32)
                                 ).astype(out_ref.dtype)


def grouped_matmul(
    lhs: jnp.ndarray,          # (m, k), rows sorted by group
    rhs: jnp.ndarray,          # (g, k, n)
    group_sizes: jnp.ndarray,  # (g,) int32, sum <= m
    *,
    tm: int = 128,
    tk: int = 512,
    tn: int = 1024,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """``(m, n)`` in ``lhs``'s dtype, accumulated in float32."""
    if interpret is None:
        interpret = default_interpret()
    m, k = lhs.shape
    g, _, n = rhs.shape
    tm, tk, tn, mp = grouped_tiles(m, k, n, tm, tk, tn)
    group_sizes = jnp.asarray(group_sizes, jnp.int32)
    xp = jnp.pad(lhs, ((0, mp - m), (0, 0))) if mp != m else lhs
    (offsets, group_ids, m_tile_ids), active = make_group_metadata(
        group_sizes=group_sizes, m=mp, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=g, visit_empty_groups=False)
    tiles_k = k // tk

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // tn, active, tiles_k),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda j, t, kk, off, gid, mid: (mid[t], kk)),
            pl.BlockSpec((None, tk, tn),
                         lambda j, t, kk, off, gid, mid: (gid[t], kk, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn),
                               lambda j, t, kk, off, gid, mid: (mid[t], j)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tn=tn, tiles_k=tiles_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name="grouped_matmul",
        interpret=interpret,
    )(offsets, group_ids, m_tile_ids, xp, rhs)
    # rows no group covers were never stored: their VMEM is whatever it was
    rows = jnp.arange(mp, dtype=jnp.int32)[:, None]
    return jnp.where(rows < offsets[g], out, 0).astype(lhs.dtype)[:m]


def grouped_matmul_xla(lhs, rhs, group_sizes, *, tm: int = 128, tk: int = 512,
                       tn: int = 1024):
    """The XLA variant (``jax.lax.ragged_dot``) under the same dispatch
    entry; it reads none of the tiles."""
    del tm, tk, tn
    return jax.lax.ragged_dot(lhs, rhs, jnp.asarray(group_sizes, jnp.int32))


def grouped_matmul_ref(lhs, rhs, group_sizes):
    """Per-group dense products in float32: the tests' oracle."""
    ends = jnp.cumsum(jnp.asarray(group_sizes, jnp.int32))
    starts = ends - group_sizes
    rows = jnp.arange(lhs.shape[0])[:, None]
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    for i in range(rhs.shape[0]):
        mine = (rows >= starts[i]) & (rows < ends[i])
        out = out + jnp.where(mine, lhs.astype(jnp.float32)
                              @ rhs[i].astype(jnp.float32), 0.0)
    return out
