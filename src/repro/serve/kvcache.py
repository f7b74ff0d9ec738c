"""KV-cache layer: byte accounting (the MLA-vs-GQA comparison numbers) and
the paged/blocked cache behind continuous-batching serving.

:class:`PagedKVCache` replaces the flat per-request dense cache for the
GQA families. The backing store is one dense buffer of ``max_batch`` slots,
but every *view* the model attends against is cut at **page granularity**:
``page_size`` — a layout axis of the tuned ``decode_attention`` space (see
``kernels.spaces``) — fixes the seq-bucket ladder, so a request that is
``pos`` tokens deep attends against ``ceil((pos+1)/page)*page`` keys, not
``max_len``. Small pages mean tight buckets (little padded attention work)
but many distinct buckets (one serve-step retrace + one dispatch signature
each); large pages the reverse — exactly the compute-vs-retrace trade the
tuner gets to own.

Requests occupy slots: :meth:`admit` copies a prefilled cache into a free
slot, decode rounds run on :meth:`view`/:meth:`writeback` batched views of
whichever slots are live (batch reshaping = picking a different slot set),
and :meth:`release` frees the slot. :meth:`stats` reports pages allocated
vs tokens resident — the paged-accounting numbers
``DispatchService.telemetry()`` surfaces under ``kv_cache``.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.models.common import ArchConfig
from repro.models.model import init_cache
from repro.obs.trace import install_jax_hooks, span

__all__ = [
    "init_cache", "cache_bytes_per_token", "cache_bytes", "PagedKVCache",
]


def cache_bytes_per_token(cfg: ArchConfig, dtype_bytes: int = 2) -> int:
    if cfg.family == "ssm":
        return 0  # state is O(1) in sequence length
    if cfg.attn_type == "mla":
        per = cfg.kv_lora_rank + cfg.qk_rope_dim
        n = cfg.n_layers
    elif cfg.family == "hybrid":
        sites = int(np.ceil(cfg.n_layers / cfg.attn_every)) if cfg.attn_every else 0
        per = 2 * cfg.n_kv_heads * cfg.hd
        n = sites
    else:
        per = 2 * cfg.n_kv_heads * cfg.hd
        n = cfg.n_layers
    return int(per * n * dtype_bytes)


def cache_bytes(cfg: ArchConfig, batch: int, seq: int, dtype_bytes: int = 2,
                page_size: int | None = None) -> int:
    """Cache footprint for ``batch`` sequences of ``seq`` tokens. With
    ``page_size`` the per-sequence length is rounded up to page granularity
    — the paged layout's allocation unit (pages are whole or nothing)."""
    if page_size:
        seq = -(-seq // page_size) * page_size
    return cache_bytes_per_token(cfg, dtype_bytes) * batch * seq


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


class PagedKVCache:
    """Slot-managed, page-bucketed KV cache for dense/GQA serving.

    Only the GQA attention families qualify: MLA keeps its latent cache,
    SSM state is O(1), and ring-buffer (sliding-window) caches already
    allocate O(window). The windowless restriction is the same static gate
    the decode dispatch route uses (``blocks.attn_layer_decode``).

    ``admit``, ``view`` and ``writeback`` are ``repro.obs`` spans
    (``kv.admit``, ``kv.view``, ``kv.writeback``) timed to their enqueue."""

    def __init__(self, cfg: ArchConfig, max_batch: int, max_len: int, *,
                 page_size: int = 128, dtype=None):
        if cfg.attn_type == "mla" or cfg.family not in ("dense", "vlm", "moe"):
            raise ValueError(f"paged KV cache requires a GQA family, got "
                             f"{cfg.family}/{cfg.attn_type}")
        if cfg.sliding_window or cfg.local_global_ratio:
            raise ValueError("paged KV cache does not support windowed archs "
                             "(their ring cache is already O(window))")
        if page_size <= 0:
            raise ValueError(f"page_size must be positive, got {page_size}")
        install_jax_hooks()
        self.cfg = cfg
        self.max_batch = int(max_batch)
        self.page_size = int(page_size)
        self.alloc = _cdiv(max_len, page_size) * page_size
        self.dtype = dtype or cfg.dtype
        self.buf = init_cache(cfg, self.max_batch, self.alloc, self.dtype)
        # host-side slot table: last written position per slot, -1 = free
        self.pos = np.full(self.max_batch, -1, np.int64)

    # -- slot management ---------------------------------------------------------

    def free_slots(self) -> list[int]:
        return [i for i in range(self.max_batch) if self.pos[i] < 0]

    def active_slots(self) -> list[int]:
        return [i for i in range(self.max_batch) if self.pos[i] >= 0]

    def admit(self, slot: int, prefilled: dict, prompt_len: int) -> None:
        """Copy a prefilled single-request cache (``init_cache(cfg, 1, n)``
        pytree, ``n <= alloc``) into ``slot``. Stale data beyond the prompt
        is harmless: decode masks by position and overwrites slot-by-slot."""
        if self.pos[slot] >= 0:
            raise ValueError(f"slot {slot} is occupied")

        def insert(buf, new):
            idx = (0,) * (buf.ndim - 4) + (slot, 0, 0, 0)
            return jax.lax.dynamic_update_slice(buf, new.astype(buf.dtype), idx)

        with span("kv.admit", slot=slot):
            self.buf = jax.tree_util.tree_map(insert, self.buf, prefilled)
        self.pos[slot] = prompt_len - 1

    def release(self, slot: int) -> None:
        self.pos[slot] = -1

    # -- bucketed batch views ----------------------------------------------------

    def seq_bucket(self, slots, extra: int = 1) -> int:
        """The page-aligned view length covering every slot's position plus
        ``extra`` upcoming tokens — the S the dispatch signature sees."""
        if len(slots) == 0:
            return self.page_size
        need = int(max(self.pos[s] for s in slots)) + 1 + extra
        return min(_cdiv(need, self.page_size) * self.page_size, self.alloc)

    def view(self, slots, bucket: int) -> dict:
        """Batched cache view over ``slots``, cut at ``bucket`` pages — what
        a decode round's serve_step consumes. A distinct (len(slots),
        bucket) shape is a distinct jit trace + dispatch signature."""
        idx = np.asarray(slots, np.int32)
        # stacked per-layer leaves are (L, B, S, K, hd); un-stacked singleton
        # sites (e.g. a moe arch's leading dense layer) are (B, S, K, hd)
        with span("kv.view", slots=len(idx), bucket=bucket):
            return jax.tree_util.tree_map(
                lambda a: a[:, idx, :bucket] if a.ndim == 5 else a[idx, :bucket],
                self.buf)

    def writeback(self, slots, bucket: int, cache: dict) -> None:
        """Scatter a round's updated view back into the backing buffer."""
        idx = np.asarray(slots, np.int32)

        def put(buf, c):
            c = c.astype(buf.dtype)
            if buf.ndim == 5:
                return buf.at[:, idx, :bucket].set(c)
            return buf.at[idx, :bucket].set(c)

        with span("kv.writeback", slots=len(idx), bucket=bucket):
            self.buf = jax.tree_util.tree_map(put, self.buf, cache)

    def pos_vector(self, slots) -> jnp.ndarray:
        """(len(slots),) int32 per-sequence decode positions."""
        return jnp.asarray([int(self.pos[s]) for s in slots], jnp.int32)

    def advance(self, slots) -> None:
        """Record one decoded token per slot (host-side position bump)."""
        for s in slots:
            self.pos[s] += 1

    # -- accounting --------------------------------------------------------------

    def stats(self) -> dict:
        """Paged accounting: pages allocated vs tokens resident. Allocation
        is page-granular per active sequence (a page is whole or nothing);
        ``bytes_backing`` is the dense backing buffer's full footprint."""
        active = self.active_slots()
        tokens = int(sum(int(self.pos[s]) + 1 for s in active))
        pages = int(sum(_cdiv(int(self.pos[s]) + 1, self.page_size)
                        for s in active))
        per_tok = cache_bytes_per_token(
            self.cfg, jnp.dtype(self.dtype).itemsize)
        cap = pages * self.page_size
        return {
            "page_size": self.page_size,
            "slots_active": len(active),
            "slots_total": self.max_batch,
            "tokens_resident": tokens,
            "pages_allocated": pages,
            "bytes_resident": tokens * per_tok,
            "bytes_allocated": cap * per_tok,
            "bytes_backing": self.max_batch * self.alloc * per_tok,
            "page_occupancy": (tokens / cap) if cap else 0.0,
        }
