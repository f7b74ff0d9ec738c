"""Training driver: end-to-end LM training on the available devices.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --steps 200 --reduced --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Fault tolerance: checkpoints every --ckpt-every steps (async), resumes from
the latest checkpoint at startup, monitors per-step stragglers. The exit
code is 1 when any step's loss is not finite.
"""

from __future__ import annotations

import argparse
import dataclasses
import math
import time

import jax
import jax.numpy as jnp

from repro.ckpt import AsyncCheckpointer, latest_step, restore
from repro.configs import get_config, get_reduced
from repro.data import SyntheticLM, make_batch
from repro.ft import StragglerMonitor
from repro.models import init_params
from repro.train import cosine_lr, init_train_state, make_train_step


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test-sized config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    opt = init_train_state(params)
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"[train] arch={cfg.name} params={n/1e6:.1f}M "
          f"steps={args.steps} batch={args.batch}x{args.seq}")

    start = 0
    ckpt = None
    if args.ckpt_dir:
        ckpt = AsyncCheckpointer(args.ckpt_dir)
        if latest_step(args.ckpt_dir) is not None:
            state, start = restore(args.ckpt_dir, {"params": params, "opt": opt})
            params, opt = state["params"], state["opt"]
            print(f"[train] resumed from step {start}")

    # params and optimizer state are replaced every step: donating them lets
    # the update reuse their buffers instead of holding two copies
    step_fn = jax.jit(make_train_step(cfg, lr=args.lr, accum=args.accum,
                                      remat=args.remat), donate_argnums=(0, 1))
    stream = SyntheticLM(cfg.vocab_size, args.seq, args.batch, seed=args.seed)
    mon = StragglerMonitor()

    t0 = time.perf_counter()
    diverged = False
    for s in range(start, args.steps):
        batch = make_batch(stream, s)
        mon.start()
        params, opt, m = step_fn(params, opt, batch)
        loss = float(m["loss"])
        dur, slow = mon.stop()
        diverged |= not math.isfinite(loss)
        if slow:
            print(f"[train] step {s}: straggler ({dur:.2f}s vs EWMA {mon.ewma:.2f}s)")
        if s % args.log_every == 0 or s == args.steps - 1:
            tok_s = args.batch * args.seq / max(dur, 1e-9)
            print(f"step {s:5d} loss {loss:.4f} "
                  f"gnorm {float(m['grad_norm']):.3f} {tok_s:,.0f} tok/s")
        if ckpt and (s + 1) % args.ckpt_every == 0:
            ckpt.save({"params": params, "opt": opt}, s + 1)
    if ckpt:
        ckpt.save({"params": params, "opt": opt}, args.steps)
        ckpt.wait()
    print(f"[train] done in {time.perf_counter()-t0:.1f}s")
    if diverged:
        print("[train] a loss was not finite")
        return 1
    return 0


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
