"""Operations and bytes of the sparse expert layer, computed from shapes.

Like :mod:`bench.flops`, these count the work the algorithm needs, not
what an implementation does: a grouped matmul multiplies each routed
(token, expert) row by its own expert's matrix once, and reads each
expert's matrix that some row needs once. Counts take a ``Dims`` of
:mod:`bench.reference.moe_gqa`. The roofline's least time of a call is
:func:`bench.flops.least_seconds`.
"""

from __future__ import annotations

__all__ = ["grouped_matmul_call", "expert_layer_calls",
           "GROUPED_MATMUL_LABEL", "GROUPED_MATMULS_PER_LAYER"]

# the kernel's label in a device trace: a Pallas call that bench/kernels.json
# does not name appears under its custom-call target
GROUPED_MATMUL_LABEL = "tpu_custom_call"
GROUPED_MATMULS_PER_LAYER = 3          # gate, up, down


def grouped_matmul_call(rows: int, k: int, n: int, groups: int,
                        itemsize: int = 2) -> tuple[float, float]:
    """One grouped matmul of ``rows`` routed rows, ``k`` wide, over
    ``groups`` experts' (k, n) matrices: FLOPs and HBM bytes. Bytes: the
    matrices of as many experts as the rows can reach, ``min(groups,
    rows)``, each read once; the rows read once and the (rows, n) result
    written once."""
    flops = 2.0 * rows * k * n
    byts = itemsize * (min(groups, rows) * k * n + rows * k + rows * n)
    return flops, byts


def expert_layer_calls(dims, batch: int) -> list[tuple[float, float]]:
    """The expert layer's three grouped matmuls (gate, up, down) for one
    decode step of ``batch`` sequences, each routed to ``top_k`` of the
    ``experts``."""
    rows, d, ff, E = batch * dims.top_k, dims.d_model, dims.d_ff, dims.experts
    return [grouped_matmul_call(rows, d, ff, E), grouped_matmul_call(rows, d, ff, E),
            grouped_matmul_call(rows, ff, d, E)]
