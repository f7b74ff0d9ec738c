"""heat-3d Pallas stencil kernel (Sec. 4.4).

PolyBench heat-3d applies a 3-axis second-difference update to the interior
of an N^3 grid, twice per time step (A->B, B->A). TPU adaptation:

  * the i (outermost) axis is grid-tiled with block size ``bi``; j and k stay
    resident in VMEM (an (bi+2h) x N x N f32 slab is a few hundred KB at
    PolyBench sizes — VMEM-friendly);
  * halo exchange uses the neighbor-block trick: the same input array is bound
    three times with index maps (i-1, i, i+1) (clamped at the edges), so each
    kernel instance sees its top/bottom halo rows without overlapping
    BlockSpecs;
  * ``fuse_t`` in {1, 2} is the *temporal blocking* knob — fuse_t=2 applies
    two time updates per HBM round trip with a 2-deep halo, halving stencil
    HBM traffic (the TPU-native analog of tiling the time loop, which is what
    Polly's default heat-3d schedule attempts on CPU).

Boundary handling is by masking with global indices, so halo garbage at the
array edges (from clamped index maps) never propagates — see the step-by-step
argument in the kernel body.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.util import cdiv, default_interpret, pad_to

__all__ = ["heat3d", "heat3d_step"]


def _masked_update(ext: jnp.ndarray, row0, n0: int) -> jnp.ndarray:
    """One masked stencil application on an extended slab.

    ``ext``: (L, N1, N2) whose row 0 has global row index ``row0``; rows
    1..L-2 get the update where their *global* row index is interior;
    everything else copies through. Rows whose global index
    falls outside [0, n0) hold garbage, but garbage only feeds rows that the
    mask forces to copy, so it never propagates into kept values.
    """
    L, n1, n2 = ext.shape
    mid = ext[1:-1]
    i_diff = ext[2:] - 2.0 * mid + ext[:-2]

    jp = jnp.concatenate([mid[:, 1:, :], mid[:, -1:, :]], axis=1)
    jm = jnp.concatenate([mid[:, :1, :], mid[:, :-1, :]], axis=1)
    j_diff = jp - 2.0 * mid + jm

    kp = jnp.concatenate([mid[:, :, 1:], mid[:, :, -1:]], axis=2)
    km = jnp.concatenate([mid[:, :, :1], mid[:, :, :-1]], axis=2)
    k_diff = kp - 2.0 * mid + km

    new = 0.125 * i_diff + 0.125 * j_diff + 0.125 * k_diff + mid

    # 3-D iotas: Mosaic cannot reshape a 1-D index vector into the slab
    gi = row0 + 1 + jax.lax.broadcasted_iota(jnp.int32, mid.shape, 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, mid.shape, 1)
    kk = jax.lax.broadcasted_iota(jnp.int32, mid.shape, 2)
    interior = (
        (gi > 0) & (gi < n0 - 1)
        & (jj > 0) & (jj < n1 - 1)
        & (kk > 0) & (kk < n2 - 1)
    )
    new = jnp.where(interior, new, mid)
    return jnp.concatenate([ext[:1], new, ext[-1:]], axis=0)


def _heat_kernel(prev_ref, cur_ref, next_ref, o_ref, *, bi: int, h: int, n0: int):
    i = pl.program_id(0)
    ext = jnp.concatenate(
        [prev_ref[...][-h:], cur_ref[...], next_ref[...][:h]], axis=0
    )  # (bi + 2h, N1, N2)
    for _ in range(h):  # fused time steps (temporal blocking)
        ext = _masked_update(ext, i * bi - h, n0)
    o_ref[...] = ext[h : h + bi]


def heat3d_step(
    A: jnp.ndarray,
    *,
    bi: int = 8,
    fuse_t: int = 1,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """``fuse_t`` masked stencil applications in one Pallas pass."""
    if interpret is None:
        interpret = default_interpret()
    n0, n1, n2 = A.shape
    bi = min(bi, n0)
    h = fuse_t
    Ap = pad_to(A, (bi, 1, 1))
    ni = Ap.shape[0] // bi

    out = pl.pallas_call(
        functools.partial(_heat_kernel, bi=bi, h=h, n0=n0),
        grid=(ni,),
        in_specs=[
            pl.BlockSpec((bi, n1, n2), lambda i: (jnp.maximum(i - 1, 0), 0, 0)),
            pl.BlockSpec((bi, n1, n2), lambda i: (i, 0, 0)),
            pl.BlockSpec((bi, n1, n2), lambda i: (jnp.minimum(i + 1, ni - 1), 0, 0)),
        ],
        out_specs=pl.BlockSpec((bi, n1, n2), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(Ap.shape, A.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        name="heat3d",
        interpret=interpret,
    )(Ap, Ap, Ap)
    return out[:n0]


def heat3d(
    A: jnp.ndarray,
    tsteps: int,
    *,
    bi: int = 8,
    fuse_t: int = 1,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """PolyBench heat-3d: 2*tsteps stencil applications (A->B->A per step)."""
    total = 2 * tsteps
    assert total % fuse_t == 0, "fuse_t must divide 2*tsteps"
    step = functools.partial(heat3d_step, bi=bi, fuse_t=fuse_t, interpret=interpret)
    return jax.lax.fori_loop(0, total // fuse_t, lambda _, x: step(x), A)
