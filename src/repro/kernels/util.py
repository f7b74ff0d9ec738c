"""Shared kernel utilities: padding, the device-derived target, alignment."""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["default_interpret", "default_target", "cdiv", "pad_to", "unpad",
           "TPU_LANE", "TPU_SUBLANE"]

TPU_LANE = 128     # last-dim tile of the TPU vector unit / MXU
TPU_SUBLANE = 8    # second-to-last-dim tile (f32)


def default_target() -> str:
    """The tuning target of the device this process runs on: ``"tpu"`` on a
    TPU (Pallas kernels, MXU-aligned tile spaces), ``"host"`` anywhere else
    (the XLA molds and the paper's CPU tile sequences)."""
    return "tpu" if jax.default_backend() == "tpu" else "host"


def default_interpret() -> bool:
    """Pallas kernels run in interpret mode exactly when no TPU is attached."""
    return jax.default_backend() != "tpu"


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def pad_to(x: jnp.ndarray, multiples: tuple[int, ...], value: float = 0.0) -> jnp.ndarray:
    """Zero-pad each dim of ``x`` up to the next multiple of ``multiples``."""
    pads = []
    for dim, m in zip(x.shape, multiples):
        target = cdiv(dim, m) * m
        pads.append((0, target - dim))
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads, constant_values=value)


def unpad(x: jnp.ndarray, shape: tuple[int, ...]) -> jnp.ndarray:
    if tuple(x.shape) == tuple(shape):
        return x
    return x[tuple(slice(0, s) for s in shape)]
