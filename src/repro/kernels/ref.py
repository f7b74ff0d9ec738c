"""Pure-jnp oracles for every kernel — the correctness ground truth.

Each function is a direct transliteration of the PolyBench 4.2 reference
computation (the code the paper's pragmas transform), with the same dataset
semantics. These are used by the per-kernel allclose tests and as the
``gcc -O3``-role baselines in the benchmark tables.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "syr2k_ref", "mm3_ref", "lu_ref", "heat3d_ref", "covariance_ref",
    "floyd_warshall_ref", "init_syr2k", "init_mm3", "init_lu", "init_heat3d",
    "init_covariance", "init_floyd_warshall", "problem_signature",
]


# ---------------------------------------------------------------------------
# syr2k: C = alpha*A@B^T + alpha*B@A^T + beta*C   (A, B: N x M; C: N x N)
# ---------------------------------------------------------------------------


def syr2k_ref(C, A, B, alpha=1.5, beta=1.2):
    return alpha * (A @ B.T) + alpha * (B @ A.T) + beta * C


def init_syr2k(N: int, M: int, dtype=jnp.float32, seed: int = 0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    A = jax.random.normal(k[0], (N, M), dtype)
    B = jax.random.normal(k[1], (N, M), dtype)
    C = jax.random.normal(k[2], (N, N), dtype)
    return C, A, B


# ---------------------------------------------------------------------------
# 3mm: G = (A @ B) @ (C @ D)
# ---------------------------------------------------------------------------


def mm3_ref(A, B, C, D):
    E = A @ B
    F = C @ D
    return E @ F


def init_mm3(P: int, Q: int, R: int, S: int, T: int, dtype=jnp.float32, seed: int = 0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    A = jax.random.normal(k[0], (P, Q), dtype) / jnp.sqrt(Q).astype(dtype)
    B = jax.random.normal(k[1], (Q, R), dtype) / jnp.sqrt(R).astype(dtype)
    C = jax.random.normal(k[2], (R, S), dtype) / jnp.sqrt(S).astype(dtype)
    D = jax.random.normal(k[3], (S, T), dtype) / jnp.sqrt(T).astype(dtype)
    return A, B, C, D


# ---------------------------------------------------------------------------
# lu: A = L*U (Doolittle, no pivoting); returns packed LU (unit L below diag)
# ---------------------------------------------------------------------------


def lu_ref(A):
    n = A.shape[0]

    def step(k, M):
        col = M[:, k]
        piv = M[k, k]
        rows = jnp.arange(n)
        m = jnp.where(rows > k, col / piv, 0.0)
        row = jnp.where(rows > k, M[k, :], 0.0)  # only cols > k get updated
        M = M - jnp.outer(m, row)
        M = M.at[:, k].set(jnp.where(rows > k, m, M[:, k]))
        return M

    return jax.lax.fori_loop(0, n, step, A)


def init_lu(N: int, dtype=jnp.float32, seed: int = 0):
    A = jax.random.normal(jax.random.PRNGKey(seed), (N, N), dtype)
    # PolyBench makes A diagonally dominant so factorization is stable
    A = A + N * jnp.eye(N, dtype=dtype)
    return (A,)


# ---------------------------------------------------------------------------
# heat-3d: TSTEPS of the PolyBench 3-axis second-difference update
# ---------------------------------------------------------------------------


def _heat3d_step(A):
    # B[i,j,k] = 0.125*(A[i+1]-2A[i]+A[i-1]) + 0.125*(j) + 0.125*(k) + A
    out = (
        0.125 * (jnp.roll(A, -1, 0) - 2.0 * A + jnp.roll(A, 1, 0))
        + 0.125 * (jnp.roll(A, -1, 1) - 2.0 * A + jnp.roll(A, 1, 1))
        + 0.125 * (jnp.roll(A, -1, 2) - 2.0 * A + jnp.roll(A, 1, 2))
        + A
    )
    n0, n1, n2 = A.shape
    ii = jnp.arange(n0)[:, None, None]
    jj = jnp.arange(n1)[None, :, None]
    kk = jnp.arange(n2)[None, None, :]
    interior = (
        (ii > 0) & (ii < n0 - 1) & (jj > 0) & (jj < n1 - 1) & (kk > 0) & (kk < n2 - 1)
    )
    return jnp.where(interior, out, A)


def heat3d_ref(A, tsteps: int):
    # PolyBench alternates A->B->A; with the masked update each pass is the
    # same operator, so 2*tsteps masked applications reproduce it.
    return jax.lax.fori_loop(0, 2 * tsteps, lambda _, x: _heat3d_step(x), A)


def init_heat3d(N: int, dtype=jnp.float32, seed: int = 0):
    A = jax.random.uniform(jax.random.PRNGKey(seed), (N, N, N), dtype)
    return (A,)


# ---------------------------------------------------------------------------
# covariance: data (N points x M attrs) -> cov (M x M)
# ---------------------------------------------------------------------------


def covariance_ref(data):
    N = data.shape[0]
    mean = data.mean(axis=0, keepdims=True)
    c = data - mean
    return (c.T @ c) / (N - 1.0)


def init_covariance(N: int, M: int, dtype=jnp.float32, seed: int = 0):
    data = jax.random.normal(jax.random.PRNGKey(seed), (N, M), dtype)
    return (data,)


# ---------------------------------------------------------------------------
# floyd-warshall: all-pairs shortest paths, min-plus relaxation over k
# ---------------------------------------------------------------------------


def floyd_warshall_ref(path):
    n = path.shape[0]

    def step(k, D):
        return jnp.minimum(D, D[:, k][:, None] + D[k, :][None, :])

    return jax.lax.fori_loop(0, n, step, path)


def init_floyd_warshall(N: int, dtype=jnp.float32, seed: int = 0):
    # PolyBench-style integer-ish edge costs; keep them positive & bounded
    w = jax.random.uniform(jax.random.PRNGKey(seed), (N, N), dtype, 1.0, 10.0)
    w = w.at[jnp.arange(N), jnp.arange(N)].set(0.0)
    return (w,)


# ---------------------------------------------------------------------------
# problem signatures: paper problem dims -> per-argument shape signature,
# mirroring the init_* array shapes above. This is the SAME signature
# repro.dispatch derives from the runtime args, so configs published from
# offline campaigns (autotune CLI --store, pallas_tuning) resolve at
# dispatch() time instead of being structurally incompatible.
# ---------------------------------------------------------------------------


def problem_signature(name: str, *dims: int) -> tuple:
    if name == "syr2k":
        N, M = dims
        return ((N, N), (N, M), (N, M))
    if name == "mm3":
        P, Q, R, S, T = dims
        return ((P, Q), (Q, R), (R, S), (S, T))
    if name == "lu":
        (N,) = dims
        return ((N, N),)
    if name == "heat3d":
        N, tsteps = dims
        return ((N, N, N), (tsteps,))
    if name == "covariance":
        N, M = dims
        return ((N, M),)
    if name == "floyd_warshall":
        (N,) = dims
        return ((N, N),)
    if name == "flash_attention":
        # trailing (2,) = the static `causal=True` kwarg the service folds in
        BH, Sq, Sk, hd = dims
        return ((BH, Sq, hd), (BH, Sk, hd), (BH, Sk, hd), (2,))
    if name == "decode_attention":
        # (BH,) = per-row cur_pos; trailing (1,), (1,) = the static
        # `ring=False`/`window=0` defaults the service folds in
        BH, G, S, hd = dims
        return ((BH, G, hd), (BH, S, hd), (BH, S, hd), (BH,), (1,), (1,))
    if name == "matmul":
        M, K, N = dims
        return ((M, K), (K, N))
    if name == "grouped_matmul":
        m, k, n, g = dims
        return ((m, k), (g, k, n), (g,))
    raise KeyError(f"unknown kernel {name!r}")
