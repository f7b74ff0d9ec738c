"""Analytic TPU cost model for the Pallas kernels — backend B2's objective
when tuning kernel schedules without TPU hardware.

For a given kernel configuration the model derives, from the BlockSpec
geometry the kernel factory would use:

  * HBM traffic: sum over grid steps of the blocks each step moves HBM<->VMEM
    (exactly what pallas_call's index maps imply — revisited blocks with an
    unchanged index map within the innermost loop stay VMEM-resident);
  * VMEM footprint: all live blocks + scratch; configurations exceeding the
    per-core budget are infeasible (returned as +inf, which the search's
    failure handling penalizes — the OOM-compile analog);
  * MXU efficiency: matmul tiles are derated by how far each dim is from the
    128x128 systolic alignment (ceil waste), plus a VPU-only path for the
    min-plus kernel (no MXU for `min`);
  * modeled seconds = max(flop_time / mxu_eff, hbm_time) — the two-term
    kernel roofline.

Validated against brute-force tile sweeps in tests (monotonic in waste,
infeasible over budget, best-known tiles score near-optimal).
"""

from __future__ import annotations

import math
from typing import Mapping

from repro.kernels.util import cdiv
from repro.perf.roofline import HW

__all__ = ["kernel_cost", "KERNEL_COST_FNS", "VMEM_BYTES"]

VMEM_BYTES = 16 * 1024 * 1024  # v5e per-core VMEM budget (model constant)
_MXU = 128
_F32 = 4
_BF16 = 2


def _align_eff(*dims: int) -> float:
    """Fraction of MXU work that is useful when each dim pads to 128/8."""
    eff = 1.0
    for i, d in enumerate(dims):
        tile = _MXU if i >= len(dims) - 2 else 8
        eff *= d / (cdiv(d, tile) * tile)
    return max(eff, 1e-3)


def _mm_cost(M, N, K, bm, bn, bk, *, dtype_bytes=_F32, extra_vmem=0.0,
             flops_factor=2.0, mxu=True):
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    mi, nj, kk = cdiv(M, bm), cdiv(N, bn), cdiv(K, bk)
    # per (i, j): A and B tiles stream over k; C written once
    hbm = mi * nj * kk * (bm * bk + bk * bn) * dtype_bytes \
        + mi * nj * bm * bn * dtype_bytes
    vmem = (bm * bk + bk * bn + bm * bn) * dtype_bytes + bm * bn * _F32 + extra_vmem
    flops = flops_factor * M * N * K
    eff = _align_eff(bm, bn, bk) if mxu else 1.0
    peak = HW.peak_flops if mxu else HW.peak_flops / 40.0  # VPU ~ MXU/40
    t = max(flops / (peak * eff), hbm / HW.hbm_bw)
    return t, hbm, vmem, flops


def _finish(t, hbm, vmem, flops):
    if vmem > VMEM_BYTES:
        return float("inf"), {"infeasible": "vmem", "vmem_bytes": vmem}
    return t, {"hbm_bytes": hbm, "vmem_bytes": vmem, "flops": flops,
               "modeled_sec": t}


def syr2k_cost(cfg: Mapping, N: int, M: int):
    bi, bj, bk = int(cfg["bi"]), int(cfg["bj"]), int(cfg["bk"])
    # two rank-k products per C tile; packing adds scratch but no HBM
    t, hbm, vmem, flops = _mm_cost(N, N, M, bi, bj, bk, flops_factor=4.0)
    hbm *= 2  # A_i/B_j and B_i/A_j streams
    if cfg.get("pack_a"):
        vmem += bi * min(bk, M) * _F32
    if cfg.get("pack_b"):
        vmem += bi * min(bk, M) * _F32
    t = max(flops / (HW.peak_flops * _align_eff(bi, bj, bk)), hbm / HW.hbm_bw)
    return _finish(t, hbm, vmem, flops)


def mm3_cost(cfg: Mapping, P: int, Q: int, R: int, S: int, T: int):
    bm, bn, bk = int(cfg["bm"]), int(cfg["bn"]), int(cfg["bk"])
    tot_t, tot_hbm, max_vmem, tot_flops = 0.0, 0.0, 0.0, 0.0
    for (m, n, k) in ((P, R, Q), (R, T, S), (P, T, R)):
        t, hbm, vmem, flops = _mm_cost(m, n, k, bm, bn, bk)
        tot_t += t
        tot_hbm += hbm
        tot_flops += flops
        max_vmem = max(max_vmem, vmem)
    return _finish(tot_t, tot_hbm, max_vmem, tot_flops)


def lu_cost(cfg: Mapping, N: int):
    bs = int(cfg["bs"])
    bm, bn = int(cfg.get("bm", 128)), int(cfg.get("bn", 128))
    nb = cdiv(N, bs)
    t = hbm = flops = 0.0
    vmem = 0.0
    for step in range(nb):
        rem = N  # full-size masked panels (static shapes)
        tt, hh, vv, ff = _mm_cost(rem, rem, bs, bm, bn, bs)
        t += tt
        hbm += hh
        flops += ff
        vmem = max(vmem, vv)
        # panel solves: O(bs^2 * N) VPU work
        flops += 2 * bs * bs * N
        t += 2 * bs * bs * N / (HW.peak_flops / 40.0)
    return _finish(t, hbm, vmem, flops)


def heat3d_cost(cfg: Mapping, N: int, tsteps: int):
    bi, fuse = int(cfg["bi"]), int(cfg.get("fuse_t", 1))
    ni = cdiv(N, bi)
    passes = 2 * tsteps // fuse
    # each pass moves (bi + 2*fuse) input slabs + bi output slabs per block
    slab = N * N * _F32
    hbm = passes * ni * ((bi + 2 * fuse) + bi) * slab
    vmem = (3 * bi + 2 * fuse) * slab  # prev/cur/next + working rows
    flops = 2 * tsteps * N * N * N * 12  # ~12 flops/point/application
    t = max(flops / (HW.peak_flops / 40.0), hbm / HW.hbm_bw)  # VPU stencil
    return _finish(t, hbm, vmem, flops)


def covariance_cost(cfg: Mapping, N: int, M: int):
    bi, bj, bk = int(cfg["bi"]), int(cfg["bj"]), int(cfg["bk"])
    t, hbm, vmem, flops = _mm_cost(M, M, N, bi, bj, bk)
    if cfg.get("fuse_center", True):
        vmem += (bi + bj) * _F32  # mean tiles
    else:
        hbm += 2 * N * M * _F32   # separate centering pass
    t = max(flops / (HW.peak_flops * _align_eff(bi, bj, bk)), hbm / HW.hbm_bw)
    return _finish(t, hbm, vmem, flops)


def floyd_warshall_cost(cfg: Mapping, N: int):
    bs, bi, bj = int(cfg["bs"]), int(cfg["bi"]), int(cfg["bj"])
    unroll = int(cfg.get("unroll", 1))
    nb = cdiv(N, bs)
    # per round: diag closure + row/col panels + full phase-3 sweep
    t3, hbm3, vmem3, flops3 = _mm_cost(N, N, bs, bi, bj, bs, mxu=False)
    hbm = nb * (hbm3 + 2 * N * bs * _F32 + bs * bs * _F32)
    flops = nb * (flops3 + 2 * N * bs * bs + bs * bs * bs)
    vmem = vmem3 + 2 * bs * max(bi, bj) * _F32
    # unrolling the k-sweep amortizes loop overhead on the VPU (up to 8)
    vpu = HW.peak_flops / 40.0 * min(1.0, 0.6 + 0.1 * unroll)
    t = max(flops / vpu, hbm / HW.hbm_bw)
    return _finish(t, hbm, vmem, flops)


def flash_attention_cost(cfg: Mapping, BH: int, Sq: int, Sk: int, hd: int):
    """The serving attention kernel. Pallas impl: q/o cross HBM once per
    q-block row, k/v stream once per q-block sweep (the kernel's BlockSpec
    index maps); XLA impl: the materializing path additionally round-trips
    the (Sq, Sk) score tensor."""
    bq, bk = min(int(cfg.get("bq", 128)), Sq), min(int(cfg.get("bk", 128)), Sk)
    impl = str(cfg.get("impl", "pallas"))
    nq, nk = cdiv(Sq, bq), cdiv(Sk, bk)
    flops = 4.0 * BH * Sq * Sk * hd  # qk^T + pv matmuls
    if impl == "xla":
        # score materialization: ~4 HBM passes over (Sq, Sk) f32 scores
        hbm = BH * (2 * Sq + 2 * Sk) * hd * _BF16 + 4 * BH * Sq * Sk * _F32
        vmem = (bq * Sk + bq * hd + Sk * hd) * _F32
        eff = _align_eff(bq, Sk, hd)
    else:
        hbm = BH * nq * (2 * bq * hd + nk * 2 * bk * hd) * _BF16
        vmem = (bq * hd + 2 * bk * hd + bq * hd) * _BF16 \
            + (bq * hd + 2 * bq) * _F32  # acc + m/l scratch
        eff = _align_eff(bq, bk, hd)
    t = max(flops / (HW.peak_flops * eff), hbm / HW.hbm_bw)
    return _finish(t, hbm, vmem, flops)


def decode_attention_cost(cfg: Mapping, BH: int, G: int, S: int, hd: int):
    """The decode hot path: one token's attention against an S-token cache.
    Memory-bound by construction — the whole KV stream crosses HBM once per
    token. ``page`` pads S up to the seq bucket the paged cache would serve
    (the layout axis's modeled cost: bigger pages mean more padded keys per
    token); ``hg`` rows share a grid cell, amortizing the q/o block DMA."""
    page = max(1, int(cfg.get("page", 128)))
    S_eff = cdiv(S, page) * page
    bk = max(1, min(int(cfg.get("bk", 128)), S_eff))
    hg = max(1, min(int(cfg.get("hg", 1)), BH))
    impl = str(cfg.get("impl", "pallas"))
    flops = 4.0 * BH * G * S_eff * hd  # qk^T + pv contractions
    if impl == "xla":
        # chunked fallback: scores stay register/cache resident per chunk,
        # but the scan re-reads q per chunk and runs f32 end to end
        nk = cdiv(S_eff, bk)
        hbm = BH * (nk * G + G) * hd * _F32 + 2 * BH * S_eff * hd * _BF16
        vmem = (G * bk + 2 * bk * hd + 2 * G * hd) * _F32
        eff = _align_eff(G, bk, hd)
    else:
        # q/o cross once per row-group; k/v stream once (the BlockSpec maps)
        hbm = 2 * BH * G * hd * _BF16 + 2 * BH * S_eff * hd * _BF16
        vmem = (hg * G * hd + 2 * bk * hd) * _BF16 \
            + (hg * G * hd + 2 * hg * G) * _F32  # acc + m/l scratch
        eff = _align_eff(hg * G, bk, hd)
    t = max(flops / (HW.peak_flops * eff), hbm / HW.hbm_bw)
    return _finish(t, hbm, vmem, flops)


def matmul_cost(cfg: Mapping, M: int, K: int, N: int):
    bm = int(cfg.get("bm", 128))
    bn = int(cfg.get("bn", 128))
    bk = int(cfg.get("bk", 128))
    t, hbm, vmem, flops = _mm_cost(M, N, K, bm, bn, bk)
    if not cfg.get("pack", False):
        hbm += cdiv(K, bk) * M * N * _F32  # o tile read-modify-written per k step
        t = max(flops / (HW.peak_flops * _align_eff(bm, bn, bk)), hbm / HW.hbm_bw)
    return _finish(t, hbm, vmem, flops)


def grouped_matmul_cost(cfg: Mapping, m: int, k: int, n: int, g: int):
    """The expert layer's grouped matmul, ``m`` rows spread over ``g``
    groups (every group non-empty where ``m >= g``). Pallas impl: the grid
    visits each (m-tile, group) pair that holds rows, at most
    ``m_tiles + groups - 1``; each visit streams that group's (k, n)
    weights through VMEM once and its lhs tile once per n-tile. XLA impl
    (``ragged_dot``): modeled as every group's weights read once."""
    from repro.kernels.grouped_matmul import grouped_tiles

    impl = str(cfg.get("impl", "pallas"))
    touched = min(g, m)
    if impl == "xla":
        hbm = (g * k * n + m * k + m * n) * _BF16
        flops = 2.0 * m * k * n
        t = max(flops / HW.peak_flops, hbm / HW.hbm_bw)
        return _finish(t, hbm, 0.0, flops)
    tm, tk, tn, mp = grouped_tiles(m, k, n, int(cfg.get("tm", 128)),
                                   int(cfg.get("tk", 512)), int(cfg.get("tn", 1024)))
    visits = mp // tm + touched - 1
    hbm = (visits * k * n + visits * tm * k * (n // tn) + mp * n) * _BF16
    # double-buffered lhs/rhs/out blocks, and the f32 accumulator
    vmem = 2 * (tm * tk + tk * tn + tm * tn) * _BF16 + tm * tn * _F32
    flops = 2.0 * visits * tm * k * n
    t = max(flops / (HW.peak_flops * _align_eff(tm, tn, tk)), hbm / HW.hbm_bw)
    return _finish(t, hbm, vmem, flops)


KERNEL_COST_FNS = {
    "syr2k": syr2k_cost,
    "mm3": mm3_cost,
    "lu": lu_cost,
    "heat3d": heat3d_cost,
    "covariance": covariance_cost,
    "floyd_warshall": floyd_warshall_cost,
    "flash_attention": flash_attention_cost,
    "decode_attention": decode_attention_cost,
    "matmul": matmul_cost,
    "grouped_matmul": grouped_matmul_cost,
}


def kernel_cost(name: str, cfg: Mapping, *shape_args):
    """Returns (modeled_seconds, info); +inf when the config cannot fit."""
    return KERNEL_COST_FNS[name](cfg, *shape_args)
