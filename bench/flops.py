"""Operations and bytes that the algorithms need, computed from shapes.

These are the yardstick's own counts: a roofline share or a utilisation
divides the least time they allow by a measured time. Each count is of the
work the algorithm needs, not of what an implementation happens to do
(padding, recomputation, copies), so a faster implementation can approach
100% but never pass it.

Dense decoder counts take a ``Dims`` of :mod:`bench.reference.dense_gqa`.
"""

from __future__ import annotations

__all__ = ["decoder_token_flops", "prefill_flops", "decode_attention_call",
           "flash_attention_call", "least_seconds"]


def _layer_matmul_params(dims) -> int:
    d, H, K, hd, ff = dims.d_model, dims.heads, dims.kv_heads, dims.head_dim, dims.d_ff
    return d * (H + 2 * K) * hd + H * hd * d + 3 * d * ff


def decoder_token_flops(dims, context: int) -> float:
    """One token through the decoder with ``context`` keys attended
    (itself included), and its logits: 2 FLOPs per matrix parameter, the
    unembedding once, and 4 * heads * head_dim per attended key per layer
    (scores and the weighted sum)."""
    L = dims.layers
    return (2.0 * L * _layer_matmul_params(dims) + 2.0 * dims.d_model * dims.vocab
            + 4.0 * L * dims.heads * dims.head_dim * context)


def prefill_flops(dims, prompt_len: int) -> float:
    """A prompt of ``prompt_len`` tokens under causal attention, and the
    logits of its last position (the first output token)."""
    L, P = dims.layers, prompt_len
    return (2.0 * L * _layer_matmul_params(dims) * P
            + 4.0 * L * dims.heads * dims.head_dim * P * (P + 1) / 2
            + 2.0 * dims.d_model * dims.vocab)


def decode_attention_call(rows: int, group: int, head_dim: int,
                          keys: int, itemsize: int = 2) -> tuple[float, float]:
    """One single-token attention call over ``rows`` (batch * kv heads)
    rows of ``group`` query heads, ``keys`` valid keys in all (summed over
    the rows): FLOPs and HBM bytes. Bytes: every valid key and value read
    once, queries read and outputs written once."""
    flops = 4.0 * group * head_dim * keys
    byts = itemsize * (2.0 * keys * head_dim + 2.0 * rows * group * head_dim)
    return flops, byts


def flash_attention_call(rows: int, seq: int, head_dim: int, causal: bool = True,
                         itemsize: int = 2) -> tuple[float, float]:
    """One prefill attention call, ``rows`` (batch * kv heads) rows of one
    query head each over ``seq`` queries and keys: FLOPs (the causal
    triangle only) and HBM bytes (Q, K, V read once, O written once)."""
    pairs = seq * (seq + 1) / 2 if causal else seq * seq
    return 4.0 * rows * head_dim * pairs, itemsize * 4.0 * rows * seq * head_dim


def least_seconds(flops: float, byts: float, peaks: dict) -> tuple[float, str]:
    """The roofline's least time for that work, and which bound sets it."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = byts / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
