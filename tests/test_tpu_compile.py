"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Every other kernel test runs the Pallas interpreter, which accepts slices
and layouts that Mosaic, the TPU's kernel compiler, refuses. These tests
compile each kernel for a described (not attached) v5e chip at the sizes
the chip paths run — the paper's six kernels at ``LARGE_SHAPES`` with
``DEFAULTS_TPU``, flash and decode attention at the qwen2-0.5b prefill and
decode shapes, and the blocked matmul — and check that the compiled program
holds the kernel (``tpu_custom_call``) rather than a fallback. Nothing runs,
so they say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, and the suite runs
under several workers that all import this file.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.matmul import tiled_matmul
from repro.kernels.problems import DEFAULTS_TPU, LARGE_SHAPES
from repro.kernels.ref import problem_signature

# the serving shapes chip_smoke.py drives: 4 requests, 128-token prompts,
# 32 new tokens (cache length 160); rows are batch * kv heads
_QWEN = get_config("qwen2-0.5b")
_BH = 4 * _QWEN.n_kv_heads
_G = _QWEN.n_heads // _QWEN.n_kv_heads
_PROMPT, _CACHE = 128, 128 + 32


def _f32(*shapes):
    return [(s, jnp.float32) for s in shapes]


def _polybench(name):
    """(fn, [(shape, dtype)]) for a PolyBench op at its LARGE shape."""
    cfg = DEFAULTS_TPU[name]
    sig = problem_signature(name, *LARGE_SHAPES[name])
    if name == "heat3d":   # the trailing signature entry is static tsteps
        fn = functools.partial(ops.heat3d_op, tsteps=sig[1][0], config=cfg,
                               interpret=False)
        return fn, _f32(sig[0])
    op = getattr(ops, f"{name}_op")
    return functools.partial(op, config=cfg, interpret=False), _f32(*sig)


CASES = {
    "decode_attention": (
        functools.partial(decode_attention, bk=DEFAULTS_TPU["decode_attention"]["bk"],
                          hg=DEFAULTS_TPU["decode_attention"]["hg"], interpret=False),
        [((_BH, _G, _QWEN.hd), jnp.bfloat16), ((_BH, _CACHE, _QWEN.hd), jnp.bfloat16),
         ((_BH, _CACHE, _QWEN.hd), jnp.bfloat16), ((_BH,), jnp.int32)]),
    "flash_attention": (
        functools.partial(flash_attention, causal=True,
                          bq=DEFAULTS_TPU["flash_attention"]["bq"],
                          bk=DEFAULTS_TPU["flash_attention"]["bk"], interpret=False),
        [((_BH, _PROMPT, _QWEN.hd), jnp.bfloat16)] * 3),
    "tiled_matmul": (
        functools.partial(tiled_matmul, **DEFAULTS_TPU["matmul"], interpret=False),
        _f32(*problem_signature("matmul", *LARGE_SHAPES["matmul"]))),
    **{name: _polybench(name) for name in
       ("syr2k", "mm3", "lu", "heat3d", "covariance", "floyd_warshall")},
}


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 host, with the persistent compile
    cache off: a compile for a described chip is written to the cache but
    cannot be read back without the chip, so later runs would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            jax.config.update("jax_enable_compilation_cache", was)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, specs = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in specs]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
