"""Per-kernel ConfigurationSpaces — the paper's pragma parameter spaces,
re-targeted at TPU schedule knobs.

Two flavors per kernel:

  * ``target="tpu"``  — MXU/VMEM-aligned tile sequences (multiples of 8/128),
    driving the Pallas kernels (backend B2 / real TPU);
  * ``target="host"`` — the paper's literal 11-entry tile sequences
    ('4'...'2048'), driving the XLA host variants (backend B1), where cache
    behavior — not the MXU — shapes the landscape, as on the paper's i7.

Space sizes mirror the paper: syr2k 2*2*2*11^3 = 10,648; 3mm 2^7 * 11^3 =
170,368; lu / covariance / heat-3d / floyd-warshall analogous.
"""

from __future__ import annotations

from repro.core.space import (
    Categorical,
    ConfigurationSpace,
    ForbiddenClause,
    InCondition,
    Ordinal,
)

__all__ = ["kernel_space", "KERNEL_SPACES"]

# the paper's tile sequences (Sec. 4.1)
HOST_TILES_A = (4, 8, 16, 20, 32, 50, 64, 80, 96, 100, 128)
HOST_TILES_B = (4, 8, 16, 20, 32, 50, 64, 80, 100, 128, 2048)
HOST_TILES_C = (4, 8, 16, 20, 32, 50, 64, 80, 100, 128, 256)
# TPU-aligned sequences: sublane/lane multiples (11 entries, like the paper)
TPU_TILES = (8, 16, 32, 64, 96, 128, 192, 256, 384, 512, 1024)
TPU_TILES_K = (16, 32, 64, 128, 192, 256, 384, 512, 768, 1024, 2048)


def _tiles(target: str, which: str):
    if target == "host":
        return {"a": HOST_TILES_A, "b": HOST_TILES_B, "c": HOST_TILES_C}[which]
    return {"a": TPU_TILES, "b": TPU_TILES_K, "c": TPU_TILES}[which]


def syr2k_space(target: str = "tpu", seed: int = 1234) -> ConfigurationSpace:
    cs = ConfigurationSpace(seed=seed)
    cs.add_hyperparameters([
        Categorical("pack_a", (True, False), default=False),
        Categorical("pack_b", (True, False), default=False),
        Categorical("interchange", (True, False), default=False),
        Ordinal("bi", _tiles(target, "a"), default=_tiles(target, "a")[8]),
        Ordinal("bk", _tiles(target, "b"), default=_tiles(target, "b")[-1]),
        Ordinal("bj", _tiles(target, "c"), default=_tiles(target, "c")[-1]),
    ])
    # the paper's CS.InCondition: consider packing B only when A is packed
    cs.add_condition(InCondition("pack_b", "pack_a", (True,)))
    return cs


def mm3_space(target: str = "tpu", seed: int = 1234) -> ConfigurationSpace:
    cs = ConfigurationSpace(seed=seed)
    cs.add_hyperparameters([
        Categorical("pack1", (True, False), default=True),
        Categorical("pack2", (True, False), default=True),
        Categorical("pack3", (True, False), default=True),
        Categorical("inter1", (True, False), default=False),
        Categorical("inter2", (True, False), default=False),
        Categorical("inter3", (True, False), default=False),
        Categorical("fuse_second", (True, False), default=False),
        Ordinal("bm", _tiles(target, "a"), default=_tiles(target, "a")[8]),
        Ordinal("bk", _tiles(target, "b"), default=_tiles(target, "b")[-1]),
        Ordinal("bn", _tiles(target, "c"), default=_tiles(target, "c")[-1]),
    ])
    return cs


def lu_space(target: str = "tpu", seed: int = 1234) -> ConfigurationSpace:
    cs = ConfigurationSpace(seed=seed)
    panel = (8, 16, 32, 64, 128) if target == "tpu" else (4, 8, 16, 32, 64)
    cs.add_hyperparameters([
        Categorical("pack", (True, False), default=True),
        Ordinal("bs", panel, default=panel[2]),
        Ordinal("bm", _tiles(target, "a"), default=_tiles(target, "a")[8]),
        Ordinal("bn", _tiles(target, "c"), default=_tiles(target, "c")[-1]),
    ])
    return cs


def heat3d_space(target: str = "tpu", seed: int = 1234) -> ConfigurationSpace:
    cs = ConfigurationSpace(seed=seed)
    bi = (1, 2, 4, 8, 16, 32) if target == "tpu" else (1, 2, 4, 8, 16, 32)
    cs.add_hyperparameters([
        Ordinal("bi", bi, default=8),
        Categorical("fuse_t", (1, 2), default=1),
    ])
    return cs


def covariance_space(target: str = "tpu", seed: int = 1234) -> ConfigurationSpace:
    cs = ConfigurationSpace(seed=seed)
    cs.add_hyperparameters([
        Categorical("fuse_center", (True, False), default=True),
        Categorical("interchange", (True, False), default=False),
        Ordinal("bi", _tiles(target, "a"), default=_tiles(target, "a")[8]),
        Ordinal("bk", _tiles(target, "b"), default=_tiles(target, "b")[-1]),
        Ordinal("bj", _tiles(target, "c"), default=_tiles(target, "c")[-1]),
    ])
    return cs


def floyd_warshall_space(target: str = "tpu", seed: int = 1234) -> ConfigurationSpace:
    cs = ConfigurationSpace(seed=seed)
    blocks = (16, 32, 64, 128, 256) if target == "tpu" else (4, 8, 16, 32, 64, 100)
    cs.add_hyperparameters([
        Ordinal("bs", blocks, default=blocks[2]),
        Ordinal("bi", _tiles(target, "a"), default=_tiles(target, "a")[8]),
        Ordinal("bj", _tiles(target, "c"), default=_tiles(target, "c")[-1]),
        Ordinal("unroll", (1, 2, 4, 8), default=1),
    ])
    return cs


# ---------------------------------------------------------------------------
# model-kernel spaces: the serving hot path's schedule knobs (beyond-paper)
# ---------------------------------------------------------------------------

# flash-attention q/k block sizes; host entries small enough for interpret mode
FLASH_TILES_TPU = (128, 256, 512, 1024)
FLASH_TILES_HOST = (16, 32, 64, 128, 256, 512)


def flash_attention_space(target: str = "tpu", seed: int = 1234) -> ConfigurationSpace:
    """Tile space over ``bq``/``bk`` plus the implementation variant axis:
    the Pallas online-softmax kernel vs the chunked-XLA fallback (which only
    reads ``bq`` as its query-chunk size)."""
    cs = ConfigurationSpace(seed=seed)
    tiles = FLASH_TILES_TPU if target == "tpu" else FLASH_TILES_HOST
    cs.add_hyperparameters([
        Categorical("impl", ("pallas", "xla"),
                    default="pallas" if target == "tpu" else "xla"),
        Ordinal("bq", tiles, default=128),
        Ordinal("bk", tiles, default=128),
    ])
    return cs


# decode KV-block tiles and paged-cache page sizes; host entries small
# enough that interpret-mode sweeps stay millisecond-scale
DECODE_TILES_TPU = (128, 256, 512, 1024)
DECODE_TILES_HOST = (8, 16, 32, 64, 128, 256)
PAGE_SIZES_TPU = (64, 128, 256, 512)
PAGE_SIZES_HOST = (8, 16, 32, 64, 128)


def decode_attention_space(target: str = "tpu", seed: int = 1234) -> ConfigurationSpace:
    """Decode-attention space: KV block ``bk``, head-grouping ``hg`` (rows
    per grid cell), the ``impl`` variant axis, and the paged KV cache's
    ``page`` size — a layout axis (arXiv 2010.06521's point that layout
    belongs in the tuned space): it fixes the seq-bucket granularity the
    dispatch signature sees, trading padded attention work against
    per-bucket retrace frequency."""
    cs = ConfigurationSpace(seed=seed)
    tiles = DECODE_TILES_TPU if target == "tpu" else DECODE_TILES_HOST
    pages = PAGE_SIZES_TPU if target == "tpu" else PAGE_SIZES_HOST
    cs.add_hyperparameters([
        Categorical("impl", ("pallas", "xla"),
                    default="pallas" if target == "tpu" else "xla"),
        Ordinal("bk", tiles, default=128),
        Ordinal("hg", (1, 2, 4, 8), default=1),
        Ordinal("page", pages, default=pages[-1]),
    ])
    return cs


def matmul_space(target: str = "tpu", seed: int = 1234) -> ConfigurationSpace:
    """Blocked-matmul space for the model projection/unembed call sites."""
    cs = ConfigurationSpace(seed=seed)
    cs.add_hyperparameters([
        Categorical("pack", (True, False), default=False),
        Categorical("interchange", (True, False), default=False),
        Ordinal("bm", _tiles(target, "a"), default=_tiles(target, "a")[8]),
        Ordinal("bk", _tiles(target, "b"), default=_tiles(target, "b")[-1]),
        Ordinal("bn", _tiles(target, "c"), default=_tiles(target, "c")[-1]),
    ])
    return cs


# grouped-matmul tiles: rows, contraction and output columns
GMM_ROWS_TPU = (16, 32, 64, 128, 256, 512)
GMM_TILES_TPU = (128, 256, 512, 1024, 2048)
GMM_ROWS_HOST = (16, 32, 64, 128)
GMM_TILES_HOST = (32, 64, 128, 256)


def grouped_matmul_space(target: str = "tpu", seed: int = 1234) -> ConfigurationSpace:
    """The expert layer's grouped matmul: row tile ``tm``, contraction tile
    ``tk``, output tile ``tn``, and the ``impl`` axis (Pallas kernel vs
    XLA's ``ragged_dot``). The TPU default streams 4 MiB weight blocks,
    which Mosaic accepts at the Mixtral-8x7B shapes
    (``tests/test_tpu_compile.py``)."""
    cs = ConfigurationSpace(seed=seed)
    rows = GMM_ROWS_TPU if target == "tpu" else GMM_ROWS_HOST
    tiles = GMM_TILES_TPU if target == "tpu" else GMM_TILES_HOST
    cs.add_hyperparameters([
        Categorical("impl", ("pallas", "xla"),
                    default="pallas" if target == "tpu" else "xla"),
        Ordinal("tm", rows, default=128),
        Ordinal("tk", tiles, default=1024 if target == "tpu" else 128),
        Ordinal("tn", tiles, default=2048 if target == "tpu" else 128),
    ])
    return cs


KERNEL_SPACES = {
    "syr2k": syr2k_space,
    "mm3": mm3_space,
    "lu": lu_space,
    "heat3d": heat3d_space,
    "covariance": covariance_space,
    "floyd_warshall": floyd_warshall_space,
    "flash_attention": flash_attention_space,
    "decode_attention": decode_attention_space,
    "matmul": matmul_space,
    "grouped_matmul": grouped_matmul_space,
}


def kernel_space(name: str, target: str = "tpu", seed: int = 1234) -> ConfigurationSpace:
    return KERNEL_SPACES[name](target=target, seed=seed)
