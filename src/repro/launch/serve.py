"""Serving driver: batched greedy decoding with a prefill + decode loop.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
        --batch 4 --prompt-len 16 --gen 32

The model runs in its configured dtype, and prefill attention, decode
attention and the projection matmuls go through a
:class:`~repro.dispatch.DispatchService`, which picks each kernel's
implementation for the device (the Pallas kernels on a TPU). Library
callers may pass their own ``service`` to read its telemetry afterwards.
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.configs import get_config, get_reduced
from repro.dispatch import DispatchService
from repro.models import init_params
from repro.serve import cache_bytes, greedy_decode


def main(argv=None, service: DispatchService | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    service = service if service is not None else DispatchService()
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    max_len = args.prompt_len + args.gen
    print(f"[serve] arch={cfg.name} batch={args.batch} "
          f"cache={cache_bytes(cfg, args.batch, max_len)/1e6:.2f} MB")

    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.prompt_len), 0, cfg.vocab_size)
    kw = {}
    if cfg.family == "audio":
        kw["enc_embed"] = jax.random.normal(
            jax.random.PRNGKey(2), (args.batch, cfg.encoder_len, cfg.d_model))

    t0 = time.perf_counter()
    out = greedy_decode(params, cfg, prompt, steps=args.gen, max_len=max_len,
                        service=service, **kw)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    print(f"[serve] generated {args.batch}x{args.gen} tokens in {dt:.2f}s "
          f"({args.batch*args.gen/dt:.1f} tok/s incl. compile)")
    for i, ids in enumerate(out.tolist()):
        print(f"[serve] request {i} ids: {ids}")
    return 0


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
