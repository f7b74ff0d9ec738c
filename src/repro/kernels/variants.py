"""Backend-B1 code molds: blocked XLA variants timed on this host.

The paper's plopper substitutes pragma strings into a C mold and times the
clang-compiled binary on an i7. Here the mold is a *blocked JAX program*
whose loop structure genuinely changes with the configuration — tile sizes
set reshape/scan extents, ``interchange`` swaps which operand is stationary,
``pack`` materializes re-laid-out operand copies through
``jax.lax.optimization_barrier`` (the copy cannot be elided, exactly like
Polly's pack-into-malloc'd-buffer) — and the measured objective is the wall
clock of the jitted executable on this machine, the same role the paper's i7
plays. Correctness of every variant is pinned to ref.py by tests.

Naming: ``<kernel>_host(config) -> (fn, args)`` factories, consumable by
``repro.core.plopper.TimingEvaluator``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Mapping

import jax
import jax.numpy as jnp

from repro.kernels import ref as R
from repro.kernels.heat3d import _masked_update
from repro.kernels.util import cdiv, pad_to, unpad

__all__ = [
    "blocked_matmul_host", "syr2k_host", "mm3_host", "lu_host", "heat3d_host",
    "covariance_host", "floyd_warshall_host", "HOST_VARIANTS", "naive_fns",
    "DISPATCH_BUILDERS", "register_dispatch_variants",
]

_bar = jax.lax.optimization_barrier


def _as_int(v) -> int:
    return int(v)


# ---------------------------------------------------------------------------
# blocked matmul (shared by 3mm / trailing updates)
# ---------------------------------------------------------------------------


def blocked_matmul_host(a, b, *, bm, bn, bk, interchange=False, pack=False):
    M, K = a.shape
    K2, N = b.shape
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    ap = pad_to(a, (bm, bk))
    bp = pad_to(b, (bk, bn))
    mi, kk = ap.shape[0] // bm, ap.shape[1] // bk
    nj = bp.shape[1] // bn

    A4 = ap.reshape(mi, bm, kk, bk).transpose(2, 0, 1, 3)  # (kk, mi, bm, bk)
    B4 = bp.reshape(kk, bk, nj, bn).transpose(0, 2, 1, 3)  # (kk, nj, bk, bn)
    if pack:  # force the re-laid-out copies to materialize
        A4, B4 = _bar((A4, B4))

    if interchange:
        # n-stationary: loop over output column blocks, full-k product each
        def jstep(_, Bj):  # Bj: (kk, bk, bn)
            return None, jnp.einsum("kmpc,kcn->mpn", A4, Bj)

        _, cols = jax.lax.scan(jstep, None, B4.transpose(1, 0, 2, 3))  # (nj, mi, bm, bn)
        out = cols.transpose(1, 2, 0, 3).reshape(ap.shape[0], bp.shape[1])
    else:
        # k-stationary accumulation: classic tiled-GEMM reduction loop
        def kstep(acc, ab):
            Ak, Bk = ab  # (mi, bm, bk), (nj, bk, bn)
            return acc + jnp.einsum("mpc,ncq->mpnq", Ak, Bk), None

        acc0 = jnp.zeros((mi, bm, nj, bn), dtype=jnp.promote_types(a.dtype, jnp.float32))
        acc, _ = jax.lax.scan(kstep, acc0, (A4, B4))
        out = acc.reshape(ap.shape[0], bp.shape[1]).astype(a.dtype)
    return unpad(out, (M, N))


# ---------------------------------------------------------------------------
# syr2k
# ---------------------------------------------------------------------------


def syr2k_variant(C, A, B, alpha, beta, *, bi, bj, bk, interchange=False,
                  pack_a=False, pack_b=False):
    N, M = A.shape
    bi, bj, bk = min(bi, N), min(bj, N), min(bk, M)
    l = math.lcm(bi, bj)
    Np = cdiv(N, l) * l
    Ap = pad_to(A, (Np, bk))
    Bp = pad_to(B, (Np, bk))
    Cp = pad_to(C, (Np, Np))
    ni, nj, kk = Np // bi, Np // bj, Ap.shape[1] // bk

    Ai = Ap.reshape(ni, bi, kk, bk).transpose(2, 0, 1, 3)  # (kk, ni, bi, bk)
    Aj = Ap.reshape(nj, bj, kk, bk).transpose(2, 0, 1, 3)
    Bi = Bp.reshape(ni, bi, kk, bk).transpose(2, 0, 1, 3)
    Bj = Bp.reshape(nj, bj, kk, bk).transpose(2, 0, 1, 3)
    if pack_a:
        Ai, Aj = _bar((Ai, Aj))
    if pack_b:
        Bi, Bj = _bar((Bi, Bj))

    lhs, rhs = ("jqc,ipc->ipjq", "jqc,ipc->ipjq") if interchange else ("ipc,jqc->ipjq",) * 2

    def kstep(acc, ops):
        ai, aj, bi_, bj_ = ops
        if interchange:
            acc = acc + alpha * jnp.einsum(lhs, bj_, ai)
            acc = acc + alpha * jnp.einsum(rhs, aj, bi_)
        else:
            acc = acc + alpha * jnp.einsum(lhs, ai, bj_)
            acc = acc + alpha * jnp.einsum(rhs, bi_, aj)
        return acc, None

    acc0 = jnp.zeros((ni, bi, nj, bj), dtype=jnp.float32)
    acc, _ = jax.lax.scan(kstep, acc0, (Ai, Aj, Bi, Bj))
    out = beta * Cp + acc.reshape(Np, Np).astype(C.dtype)
    return unpad(out, (N, N))


# ---------------------------------------------------------------------------
# covariance
# ---------------------------------------------------------------------------


def covariance_variant(data, *, bi, bj, bk, fuse_center=True, interchange=False):
    Nn, M = data.shape
    bi, bj, bk = min(bi, M), min(bj, M), min(bk, Nn)
    mean = data.mean(axis=0, keepdims=True)
    if not fuse_center:
        data = data - mean
    l = math.lcm(bi, bj)
    Mp = cdiv(M, l) * l
    dp = pad_to(data, (bk, Mp))
    if fuse_center and dp.shape[0] != Nn:
        filler = jnp.broadcast_to(pad_to(mean, (1, Mp)), (dp.shape[0] - Nn, Mp))
        dp = dp.at[Nn:, :].set(filler)
    mp = pad_to(mean, (1, Mp))
    kk = dp.shape[0] // bk
    ni, nj = Mp // bi, Mp // bj

    Di = dp.reshape(kk, bk, ni, bi).transpose(0, 2, 1, 3)  # (kk, ni, bk, bi)
    Dj = dp.reshape(kk, bk, nj, bj).transpose(0, 2, 1, 3)
    Mi = mp.reshape(1, ni, bi)
    Mj = mp.reshape(1, nj, bj)

    def kstep(acc, ops):
        di, dj = ops
        if fuse_center:
            di = di - Mi[0][:, None, :]
            dj = dj - Mj[0][:, None, :]
        ein = "jcq,icp->jqip" if interchange else "icp,jcq->ipjq"
        if interchange:
            acc = acc + jnp.einsum(ein, dj, di).transpose(2, 3, 0, 1)
        else:
            acc = acc + jnp.einsum(ein, di, dj)
        return acc, None

    acc0 = jnp.zeros((ni, bi, nj, bj), dtype=jnp.float32)
    acc, _ = jax.lax.scan(kstep, acc0, (Di, Dj))
    out = (acc.reshape(Mp, Mp) / (Nn - 1.0)).astype(data.dtype)
    return unpad(out, (M, M))


# ---------------------------------------------------------------------------
# heat-3d (blocked over i with halos, shared masked-update helper)
# ---------------------------------------------------------------------------


def heat3d_variant(A, tsteps, *, bi, fuse_t=1):
    n0, n1, n2 = A.shape
    bi = min(bi, n0)
    h = fuse_t
    total = 2 * tsteps
    assert total % h == 0
    Ap = pad_to(A, (bi, 1, 1))
    ni = Ap.shape[0] // bi
    Npad = Ap.shape[0]

    def one_pass(X):
        Xh = jnp.pad(X, ((h, h), (0, 0), (0, 0)))

        def block(i):
            ext = jax.lax.dynamic_slice(Xh, (i * bi, 0, 0), (bi + 2 * h, n1, n2))
            e = ext
            for _ in range(h):
                e = _masked_update(e, i * bi - h, n0)
            return e[h : h + bi]

        blocks = jax.lax.map(block, jnp.arange(ni))
        return blocks.reshape(Npad, n1, n2)

    out = jax.lax.fori_loop(0, total // h, lambda _, x: one_pass(x), Ap)
    return out[:n0]


# ---------------------------------------------------------------------------
# lu / floyd-warshall: the blocked wrappers already support an XLA inner path
# ---------------------------------------------------------------------------


def lu_variant(A, *, bs, bm=128, bn=128, pack=True):
    from repro.kernels.lu import lu

    return lu(A, bs=bs, bm=bm, bn=bn, pack=pack, matmul_impl="xla")


def _minplus_xla(D, A, B, chunk: int):
    """min(D, A (x) B) with the k-reduction chunked (``chunk`` = unroll)."""
    n, m = D.shape
    bsz = A.shape[1]
    chunk = min(chunk, bsz)
    pad = (-bsz) % chunk
    if pad:
        A = jnp.pad(A, ((0, 0), (0, pad)), constant_values=1e18)
        B = jnp.pad(B, ((0, pad), (0, 0)), constant_values=1e18)
    kc = A.shape[1] // chunk
    Ac = A.reshape(n, kc, chunk).transpose(1, 0, 2)  # (kc, n, chunk)
    Bc = B.reshape(kc, chunk, m)

    def step(acc, ab):
        a, b = ab  # (n, chunk), (chunk, m)
        return jnp.minimum(acc, (a[:, :, None] + b[None, :, :]).min(axis=1)), None

    out, _ = jax.lax.scan(step, D, (Ac, Bc))
    return out


def floyd_warshall_variant(path, *, bs, bi=128, bj=128, unroll=1):
    N = path.shape[0]
    bs = min(bs, N)
    BIG = 1.0e18
    Dp = pad_to(path, (bs, bs), value=BIG)
    Np = Dp.shape[0]
    nb = Np // bs

    def closure(Dk):
        def s(k, M):
            return jnp.minimum(M, M[:, k][:, None] + M[k, :][None, :])
        return jax.lax.fori_loop(0, bs, s, Dk)

    def block_round(kb, D):
        off = kb * bs
        diag = closure(jax.lax.dynamic_slice(D, (off, off), (bs, bs)))
        D = jax.lax.dynamic_update_slice(D, diag, (off, off))
        row = jax.lax.dynamic_slice(D, (off, 0), (bs, Np))
        row = _minplus_xla(row, diag, row, unroll)
        D = jax.lax.dynamic_update_slice(D, row, (off, 0))
        col = jax.lax.dynamic_slice(D, (0, off), (Np, bs))
        col = _minplus_xla(col, col, diag, unroll)
        D = jax.lax.dynamic_update_slice(D, col, (0, off))
        return _minplus_xla(D, col, row, unroll)

    out = jax.lax.fori_loop(0, nb, block_round, Dp)
    return out[:N, :N]


# ---------------------------------------------------------------------------
# builders: config (+ static knobs) -> fn(*arrays). One shared definition
# feeds both the TimingEvaluator factories below and the dispatch registry.
# ---------------------------------------------------------------------------


def _ints(cfg: Mapping[str, Any], *names) -> dict:
    return {n: _as_int(cfg[n]) for n in names if n in cfg}


def syr2k_builder(cfg: Mapping[str, Any]):
    kw = _ints(cfg, "bi", "bj", "bk")
    kw.update(interchange=bool(cfg.get("interchange", False)),
              pack_a=bool(cfg.get("pack_a", False)),
              pack_b=bool(cfg.get("pack_b", False)))
    return functools.partial(syr2k_variant, alpha=1.5, beta=1.2, **kw)


def mm3_builder(cfg: Mapping[str, Any]):
    kw = _ints(cfg, "bm", "bn", "bk")

    def fn(a, b, c, d):
        E = blocked_matmul_host(a, b, pack=bool(cfg.get("pack1", True)),
                                interchange=bool(cfg.get("inter1", False)), **kw)
        F = blocked_matmul_host(c, d, pack=bool(cfg.get("pack2", True)),
                                interchange=bool(cfg.get("inter2", False)), **kw)
        return blocked_matmul_host(E, F, pack=bool(cfg.get("pack3", True)),
                                   interchange=bool(cfg.get("inter3", False)), **kw)

    return fn


def lu_builder(cfg: Mapping[str, Any]):
    kw = _ints(cfg, "bs", "bm", "bn")
    return functools.partial(lu_variant, pack=bool(cfg.get("pack", True)), **kw)


def heat3d_builder(cfg: Mapping[str, Any], tsteps: int = 8):
    return functools.partial(heat3d_variant, tsteps=tsteps,
                             bi=_as_int(cfg["bi"]), fuse_t=_as_int(cfg.get("fuse_t", 1)))


def covariance_builder(cfg: Mapping[str, Any]):
    kw = _ints(cfg, "bi", "bj", "bk")
    return functools.partial(covariance_variant,
                             fuse_center=bool(cfg.get("fuse_center", True)),
                             interchange=bool(cfg.get("interchange", False)), **kw)


def floyd_warshall_builder(cfg: Mapping[str, Any]):
    return functools.partial(floyd_warshall_variant,
                             **_ints(cfg, "bs", "bi", "bj", "unroll"))


DISPATCH_BUILDERS = {
    "syr2k": syr2k_builder,
    "mm3": mm3_builder,
    "lu": lu_builder,
    "heat3d": heat3d_builder,
    "covariance": covariance_builder,
    "floyd_warshall": floyd_warshall_builder,
}


def register_dispatch_variants() -> None:
    """Register every host kernel into the repro.dispatch registry (called
    lazily by the registry itself, idempotent by construction)."""
    from repro.dispatch.registry import register
    from repro.kernels.spaces import kernel_space

    for name, builder in DISPATCH_BUILDERS.items():
        register(name, builder,
                 space=functools.partial(kernel_space, name))


# ---------------------------------------------------------------------------
# factories: kernel name -> (factory(config) -> (fn, args)) for TimingEvaluator
# ---------------------------------------------------------------------------


def _host_factory(builder, problem, **static_kw):
    def factory(cfg):
        return builder(cfg, **static_kw), problem

    return factory


def syr2k_host(problem):
    return _host_factory(syr2k_builder, problem)


def mm3_host(problem):
    return _host_factory(mm3_builder, problem)


def lu_host(problem):
    return _host_factory(lu_builder, problem)


def heat3d_host(problem, tsteps):
    return _host_factory(heat3d_builder, problem, tsteps=tsteps)


def covariance_host(problem):
    return _host_factory(covariance_builder, problem)


def floyd_warshall_host(problem):
    return _host_factory(floyd_warshall_builder, problem)


HOST_VARIANTS = {
    "syr2k": syr2k_host,
    "mm3": mm3_host,
    "lu": lu_host,
    "heat3d": heat3d_host,
    "covariance": covariance_host,
    "floyd_warshall": floyd_warshall_host,
}


def naive_fns():
    """The untransformed loop nests (the 'gcc -O3 on the original code' row):
    row-at-a-time fori loops — compiled, but neither tiled nor library-lowered."""

    def naive_matvec_rows(a, b):
        M = a.shape[0]

        def row(i, acc):
            return acc.at[i, :].set(a[i, :] @ b)

        return jax.lax.fori_loop(0, M, row, jnp.zeros((M, b.shape[1]), a.dtype))

    def syr2k(C, A, B):
        N = A.shape[0]

        def row(i, acc):
            v = 1.5 * (A[i, :] @ B.T) + 1.5 * (B[i, :] @ A.T) + 1.2 * C[i, :]
            return acc.at[i, :].set(v)

        return jax.lax.fori_loop(0, N, row, jnp.zeros_like(C))

    def mm3(A, B, C, D):
        E = naive_matvec_rows(A, B)
        F = naive_matvec_rows(C, D)
        return naive_matvec_rows(E, F)

    def covariance(data):
        Nn, M = data.shape
        c = data - data.mean(axis=0, keepdims=True)

        def row(i, acc):
            return acc.at[i, :].set(c[:, i] @ c / (Nn - 1.0))

        return jax.lax.fori_loop(0, M, row, jnp.zeros((M, M), data.dtype))

    return {
        "syr2k": syr2k,
        "mm3": mm3,
        "lu": R.lu_ref,
        "heat3d": R.heat3d_ref,
        "covariance": covariance,
        "floyd_warshall": R.floyd_warshall_ref,
    }
