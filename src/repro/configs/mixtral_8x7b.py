"""mixtral-8x7b [moe] — 32L d_model=4096 32H (GQA kv=8) d_ff=14336
vocab=32000, 8 experts top-2, dense attention over a 32k context
(the published ``sliding_window`` is null). [arXiv:2401.04088; hf]"""

from repro.models.common import ArchConfig

CONFIG = ArchConfig(
    name="mixtral-8x7b",
    family="moe",
    n_layers=32,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=14336,
    moe_d_ff=14336,
    vocab_size=32000,
    sliding_window=None,
    n_experts=8,
    top_k=2,
    rope_theta=1.0e6,
    rms_eps=1.0e-5,
    tie_embeddings=False,
)


def reduced() -> ArchConfig:
    import dataclasses
    return dataclasses.replace(
        CONFIG, n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=96, moe_d_ff=96, vocab_size=256, n_experts=4, top_k=2)
