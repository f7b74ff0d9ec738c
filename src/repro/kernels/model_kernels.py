"""Model-kernel dispatch builders: the serving hot path's tunable kernels.

The PolyBench kernels route through :mod:`repro.dispatch` via
``kernels.variants``; this module does the same for the kernels the model
stack actually serves — flash attention (``bq``/``bk`` VMEM tiles, plus the
chunked-XLA fallback as an ``impl`` variant axis) and the blocked matmul
behind the projection/unembed call sites. ``repro.models`` reaches these
through the ``service=`` path (see ``models.attention``), so prefill/decode
resolve tuned block shapes per shape signature instead of hard-coding the
kernel defaults.

Signature scheme: the dispatch service derives signatures from the runtime
arrays plus sorted static kwargs, so a flash call is keyed
``((BH, Sq, hd), (BH, Sk, hd), (BH, Sk, hd), (2,))`` — the trailing dim is
the static ``causal`` flag ((2,) causal, (1,) not). ``BH`` is batch times
kv heads: the GQA route dispatches per kv-head group (see
``models.attention``), so MHA and GQA key consistently.
:func:`flash_attention_signature` builds that key for offline publishers
(campaigns, tests) so their records resolve at dispatch time.
"""

from __future__ import annotations

import functools
from typing import Any, Mapping

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import chunked_decode_xla, decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.grouped_matmul import grouped_matmul, grouped_matmul_xla
from repro.kernels.variants import blocked_matmul_host

__all__ = [
    "chunked_attention_xla", "flash_attention_builder", "matmul_builder",
    "decode_attention_builder", "decode_attention_signature",
    "flash_attention_signature", "init_flash_attention", "init_matmul",
    "init_decode_attention", "decode_attention_host",
    "flash_attention_host", "matmul_host", "grouped_matmul_builder",
    "init_grouped_matmul", "grouped_matmul_host", "MODEL_KERNEL_BUILDERS",
    "register_model_kernels",
]

_NEG = -1.0e30


def chunked_attention_xla(
    q: jnp.ndarray,            # (BH, Sq, hd) — batch*heads flattened
    k: jnp.ndarray,            # (BH, Sk, hd)
    v: jnp.ndarray,            # (BH, Sk, hd)
    *,
    causal: bool = True,
    bq: int = 128,
    scale: float | None = None,
) -> jnp.ndarray:
    """The materializing fallback: per q-chunk full-score softmax in f32.
    Same contract as :func:`~repro.kernels.flash_attention.flash_attention`
    so the two are interchangeable variants under one dispatch entry."""
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    scale = scale if scale is not None else hd ** -0.5
    bq = min(bq, Sq)
    pad = (-Sq) % bq
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0))) if pad else q
    nq = qp.shape[1] // bq
    qc = qp.reshape(BH, nq, bq, hd).transpose(1, 0, 2, 3)   # (nq, BH, bq, hd)
    kpos = jnp.arange(Sk)

    def one_chunk(ci, qblk):
        s = jnp.einsum("bqh,bsh->bqs", qblk.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        if causal:
            qpos = ci * bq + jnp.arange(bq)
            s = jnp.where(qpos[None, :, None] >= kpos[None, None, :], s, _NEG)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bqs,bsh->bqh", p, v.astype(jnp.float32))
        return o.astype(q.dtype)

    out = jax.lax.map(lambda xs: one_chunk(*xs), (jnp.arange(nq), qc))
    out = out.transpose(1, 0, 2, 3).reshape(BH, nq * bq, hd)
    return out[:, :Sq]


# ---------------------------------------------------------------------------
# dispatch builders: config (+ static kwargs) -> fn(*arrays)
# ---------------------------------------------------------------------------


def flash_attention_builder(cfg: Mapping[str, Any], *, causal: bool = True):
    impl = str(cfg.get("impl", "pallas"))
    bq, bk = int(cfg.get("bq", 128)), int(cfg.get("bk", 128))
    if impl == "xla":
        return functools.partial(chunked_attention_xla, causal=causal, bq=bq)
    if impl == "pallas":
        return functools.partial(flash_attention, causal=causal, bq=bq, bk=bk)
    raise ValueError(f"unknown flash_attention impl {impl!r}")


def decode_attention_builder(cfg: Mapping[str, Any], *, ring: bool = False,
                             window: int = 0):
    """Decode-attention variants under one dispatch entry. ``page`` is part
    of the tuned config but is a *layout* axis realized by the paged KV
    cache (it decides the seq bucket the signature's S lands on), so the
    builder ignores it — both impls read the cache view they are handed."""
    impl = str(cfg.get("impl", "pallas"))
    bk, hg = int(cfg.get("bk", 128)), int(cfg.get("hg", 1))
    if impl == "xla":
        return functools.partial(chunked_decode_xla, ring=ring, window=window,
                                 bk=bk)
    if impl == "pallas":
        return functools.partial(decode_attention, ring=ring, window=window,
                                 bk=bk, hg=hg)
    raise ValueError(f"unknown decode_attention impl {impl!r}")


def matmul_builder(cfg: Mapping[str, Any]):
    return functools.partial(
        blocked_matmul_host,
        bm=int(cfg.get("bm", 128)), bn=int(cfg.get("bn", 128)),
        bk=int(cfg.get("bk", 128)),
        interchange=bool(cfg.get("interchange", False)),
        pack=bool(cfg.get("pack", False)))


def grouped_matmul_builder(cfg: Mapping[str, Any]):
    """The expert layer's grouped matmul: the Pallas kernel with its
    ``tm``/``tk``/``tn`` tiles, or XLA's ``ragged_dot``."""
    impl = str(cfg.get("impl", "pallas"))
    tiles = {t: int(cfg.get(t, d)) for t, d in (("tm", 128), ("tk", 512), ("tn", 1024))}
    if impl == "xla":
        return functools.partial(grouped_matmul_xla, **tiles)
    if impl == "pallas":
        return functools.partial(grouped_matmul, **tiles)
    raise ValueError(f"unknown grouped_matmul impl {impl!r}")


MODEL_KERNEL_BUILDERS = {
    "flash_attention": flash_attention_builder,
    "decode_attention": decode_attention_builder,
    "matmul": matmul_builder,
    "grouped_matmul": grouped_matmul_builder,
}


def register_model_kernels() -> None:
    """Register the model kernels into the repro.dispatch registry (called
    lazily by the registry itself, idempotent by construction)."""
    from repro.dispatch.registry import register
    from repro.kernels.spaces import kernel_space

    for name, builder in MODEL_KERNEL_BUILDERS.items():
        register(name, builder, space=functools.partial(kernel_space, name))


# ---------------------------------------------------------------------------
# store-signature / problem helpers (offline campaigns, CLI, tests)
# ---------------------------------------------------------------------------


def flash_attention_signature(BH: int, Sq: int, Sk: int, hd: int,
                              causal: bool = True) -> tuple:
    """The signature ``service.dispatch('flash_attention', q, k, v,
    causal=...)`` derives at runtime; the trailing dim is the static
    ``causal`` kwarg folded into the signature ((2,) = causal, (1,) = not —
    the two masking modes must not share tuned records)."""
    return ((BH, Sq, hd), (BH, Sk, hd), (BH, Sk, hd), (2,) if causal else (1,))


def init_flash_attention(BH: int, Sq: int, Sk: int, hd: int,
                         dtype=jnp.float32, seed: int = 0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (BH, Sq, hd), dtype)
    k = jax.random.normal(ks[1], (BH, Sk, hd), dtype)
    v = jax.random.normal(ks[2], (BH, Sk, hd), dtype)
    return q, k, v


def decode_attention_signature(BH: int, G: int, S: int, hd: int,
                               *, ring: bool = False, window: int = 0) -> tuple:
    """The signature ``service.dispatch('decode_attention', q, k, v,
    cur_pos, ring=..., window=...)`` derives at runtime. ``BH`` is batch
    times kv heads (the GQA route flattens per kv-head rows, G query heads
    ride along as a dense axis); ``S`` is the *seq bucket* — the paged
    cache's view length, always a multiple of the tuned ``page``. The
    (BH,) entry is the per-row ``cur_pos`` vector; the trailing dims are
    the static ``ring``/``window`` kwargs folded in sorted order ((2,) =
    ring, (1,) = linear; window clamps to (1,) when disabled)."""
    return ((BH, G, hd), (BH, S, hd), (BH, S, hd), (BH,),
            (2,) if ring else (1,), (max(1, int(window)),))


def init_decode_attention(BH: int, G: int, S: int, hd: int,
                          dtype=jnp.float32, seed: int = 0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (BH, G, hd), dtype)
    k = jax.random.normal(ks[1], (BH, S, hd), dtype)
    v = jax.random.normal(ks[2], (BH, S, hd), dtype)
    cur_pos = jnp.full((BH,), S - 1, jnp.int32)   # fully-resident cache
    return q, k, v, cur_pos


def init_matmul(M: int, K: int, N: int, dtype=jnp.float32, seed: int = 0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    a = jax.random.normal(ks[0], (M, K), dtype) / jnp.sqrt(K).astype(dtype)
    b = jax.random.normal(ks[1], (K, N), dtype) / jnp.sqrt(N).astype(dtype)
    return a, b


def init_grouped_matmul(m: int, k: int, n: int, g: int, dtype=jnp.float32,
                        seed: int = 0):
    """``(lhs, rhs, group_sizes)``: ``m`` rows over ``g`` groups of uneven
    sizes, the second group empty (a decode step that routes no token to an
    expert), as the expert layer hands the kernel."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    lhs = jax.random.normal(ks[0], (m, k), dtype)
    rhs = jax.random.normal(ks[1], (g, k, n), dtype) / jnp.sqrt(k).astype(dtype)
    weights = [0 if i == 1 else i + 1 for i in range(g)]
    sizes = [m * w // sum(weights) for w in weights]
    sizes[-1] += m - sum(sizes)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32)


def flash_attention_host(problem):
    def factory(cfg):
        return flash_attention_builder(cfg), problem

    return factory


def decode_attention_host(problem):
    def factory(cfg):
        return decode_attention_builder(cfg), problem

    return factory


def matmul_host(problem):
    def factory(cfg):
        return matmul_builder(cfg), problem

    return factory


def grouped_matmul_host(problem):
    def factory(cfg):
        return grouped_matmul_builder(cfg), problem

    return factory
