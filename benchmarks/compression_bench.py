"""Gradient-compression roofline measurement: lower a shard_map data-parallel
training step with f32 / bf16 / int8 gradient all-reduce payloads and walk
the compiled HLO — the wire-format bytes must shrink 1x / 2x / 4x, which is
the cross-pod collective-term lever the §Perf narrative banks for
collective-bound cells.

Error-feedback correctness of the compressed path is covered by
tests/test_ckpt_ft.py; this file quantifies the traffic.

    PYTHONPATH=src:. python -m benchmarks.compression_bench
"""

from __future__ import annotations

import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import dataclasses  # noqa: E402
import functools    # noqa: E402
import json         # noqa: E402

import jax                     # noqa: E402
import jax.numpy as jnp        # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402


def build_step(cfg, mode: str, mesh):
    """Pure-DP step via shard_map: replicated params, sharded batch, explicit
    gradient all-reduce whose payload dtype is the knob."""
    from jax import shard_map

    from repro.ft.compression import compressed_psum
    from repro.models.model import loss_fn

    def per_shard(params, batch):
        (total, _), grads = jax.value_and_grad(
            functools.partial(loss_fn, cfg=cfg), has_aux=True)(params, batch)

        def reduce_leaf(g):
            g32 = g.astype(jnp.float32)
            if mode == "f32":
                return jax.lax.psum(g32, "data")
            if mode == "bf16":
                return jax.lax.psum(g32.astype(jnp.bfloat16), "data").astype(jnp.float32)
            return compressed_psum(g32, "data")  # int8 + max-scale combine

        grads = jax.tree_util.tree_map(reduce_leaf, grads)
        return jax.lax.pmean(total, "data"), grads

    return shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(), {"tokens": P("data", None), "labels": P("data", None)}),
        out_specs=(P(), P()),
    )


def main():
    from repro.configs import get_reduced
    from repro.models.model import init_params
    from repro.perf.hlo_cost import module_cost

    mesh = jax.make_mesh((8,), ("data",))
    cfg = dataclasses.replace(get_reduced("qwen1.5-0.5b"), dtype=jnp.float32)
    params = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0)))
    batch = {
        "tokens": jax.ShapeDtypeStruct((16, 64), jnp.int32),
        "labels": jax.ShapeDtypeStruct((16, 64), jnp.int32),
    }
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))

    rows = {}
    for mode in ("f32", "bf16", "int8"):
        step = build_step(cfg, mode, mesh)
        with mesh:
            compiled = jax.jit(step).lower(params, batch).compile()
        cost = module_cost(compiled.as_text())
        ar = cost.coll_by_kind.get("all-reduce", 0.0)
        rows[mode] = {"all_reduce_bytes": ar,
                      "bytes_per_param": ar / n_params,
                      "total_collective_bytes": cost.collective_bytes}
        print(f"{mode:5s} all-reduce payload: {ar/1e6:8.2f} MB "
              f"({ar/n_params:5.2f} B/param)")

    r = rows
    print(f"bf16 saves {1 - r['bf16']['all_reduce_bytes']/r['f32']['all_reduce_bytes']:.0%}, "
          f"int8 saves {1 - r['int8']['all_reduce_bytes']/r['f32']['all_reduce_bytes']:.0%} "
          f"of gradient all-reduce traffic")
    os.makedirs("results", exist_ok=True)
    with open("results/compression_bench.json", "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache

    enable_compile_cache()
    main()
