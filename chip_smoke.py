"""Proof that the system's main paths run on one TPU chip.

    python chip_smoke.py

One process, three phases, each through the entry point a user would call:

  * paper loop — ``repro.launch.autotune`` runs a short RF campaign on
    syr2k at the paper's LARGE size (1200 x 1000); every evaluation compiles
    and times the Pallas kernel on the chip. The ``DEFAULTS_TPU`` config and
    the campaign's best config must both match the reference;
  * serving — ``repro.launch.serve`` serves qwen2-0.5b at its published
    widths and dtype (bf16) through a ``DispatchService``: 4 requests with
    128-token prompts and 32 new tokens. Prefill and decode attention must
    run the Pallas kernels, and the first decode step's logits must match
    the path without the service;
  * training — ``repro.launch.train`` takes 3 steps on full-width
    qwen2-0.5b; every loss must be finite.

Without a TPU it exits non-zero before any phase. The last line of standard
output is one JSON object, ``{"ok": true, "device": {...}}``, printed only
when every phase passed. Times printed on the way are host-clock seconds
including compilation, not device measurements.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
import traceback
from collections import Counter

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

ARCH = "qwen2-0.5b"

# syr2k: relative Frobenius error of the kernel against the float32
# reference computed at "highest" matmul precision. Mosaic feeds f32
# operands to the MXU as one bf16 pass (unit roundoff 2**-9 per operand);
# over the M = 1000 random-sign products of each output that gives a
# relative error near 2**-8 = 3.9e-3 in norm (2.3e-3 measured on a TPU
# v5e). 1e-2 leaves margin, while a wrong tile or a dropped contraction
# block errs by 0.1 or more.
SYR2K_TOL = 1e-2

# first decode step, service path vs the service-free path: both run the
# bf16 model with f32 accumulation but round at different points (the
# kernel keeps probabilities in f32, the einsum path rounds them to bf16;
# the blocked matmul accumulates per block). bf16's unit roundoff is
# 2**-9 = 2e-3, and the relative norm of the difference grows slowly with
# depth: 0.009 at 2 layers and 0.015 at 24 layers on narrow copies of this
# model run on a CPU. 5e-2 leaves 3x margin; a mask or layout bug errs by
# O(1).
LOGITS_TOL = 5e-2


class PhaseFailed(Exception):
    pass


def _rel_err(got, want) -> float:
    import jax.numpy as jnp

    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def phase_autotune(evals: int = 8, learner: str = "RF", seed: int = 1234) -> dict:
    """The paper's loop on syr2k at ``LARGE_SHAPES``, timed on the device."""
    import jax

    from repro.core.database import OK, PerformanceDatabase
    from repro.core.plopper import TimingEvaluator
    from repro.kernels.problems import DEFAULTS_TPU, LARGE_SHAPES, tpu_problem
    from repro.kernels.ref import syr2k_ref
    from repro.launch import autotune

    factory = tpu_problem("syr2k")
    _, args = factory({})
    with jax.default_matmul_precision("highest"):
        want = jax.jit(syr2k_ref)(*args)

    def error_of(cfg) -> float:
        fn, a = factory(cfg)
        return _rel_err(jax.jit(fn)(*a), want)

    default = TimingEvaluator(factory, repeats=2, warmup=1)(DEFAULTS_TPU["syr2k"])
    if not default.ok:
        raise PhaseFailed(f"DEFAULTS_TPU syr2k failed: {default.info['error']}")
    default_err = error_of(DEFAULTS_TPU["syr2k"])
    if not default_err <= SYR2K_TOL:
        raise PhaseFailed(f"DEFAULTS_TPU syr2k error {default_err} > {SYR2K_TOL}")

    with tempfile.TemporaryDirectory() as db:
        rc = autotune.main(["--kernel", "syr2k", "--max-evals", str(evals),
                            "--learner", learner, "--seed", str(seed),
                            "--db", db])
        records = PerformanceDatabase(db).records
    ok = [r for r in records if r.status == OK]
    errors = [r.info.get("error", "unknown") for r in records if r.status != OK]
    failed = Counter(e.split(":")[0] for e in errors)
    # one message per class, first line only: Mosaic's are pages long
    examples = {e.split(":")[0]: e.splitlines()[0][:200] for e in reversed(errors)}
    if rc != 0 or not ok:
        raise PhaseFailed(f"campaign exit code {rc}, {len(ok)} evaluation(s) "
                          f"succeeded, failures {dict(failed)}")
    best = min(ok, key=lambda r: r.objective)
    best_err = error_of(best.config)
    compile_sec = [r.info["compile_sec"] for r in ok]
    summary = {
        "dims": list(LARGE_SHAPES["syr2k"]),
        "evaluations": len(records),
        "failed_by_class": dict(failed),
        "failure_examples": examples,
        "default_sec": default.objective,
        "default_compile_sec": default.info["compile_sec"],
        "default_rel_err": default_err,
        "best_sec": best.objective,
        "best_config": best.config,
        "best_rel_err": best_err,
        "compile_sec_total": sum(compile_sec),
        "compile_sec_max": max(compile_sec),
        "tolerance": SYR2K_TOL,
    }
    if not best_err <= SYR2K_TOL:
        raise PhaseFailed(f"best config {best.config} error {best_err} > "
                          f"{SYR2K_TOL}")
    return summary


def phase_serving(*, reduced: bool = False, batch: int = 4,
                  prompt_len: int = 128, gen: int = 32, seed: int = 0,
                  service=None) -> dict:
    """Serve ``ARCH`` through a DispatchService; the attention kernels must
    be the Pallas ones, and the first decode step must match the
    service-free path."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, get_reduced
    from repro.dispatch import DispatchService
    from repro.launch import serve
    from repro.models import init_params
    from repro.serve import make_serve_step, prefill

    svc = service if service is not None else DispatchService()
    argv = ["--arch", ARCH, "--batch", str(batch), "--prompt-len",
            str(prompt_len), "--gen", str(gen), "--seed", str(seed)]
    rc = serve.main(argv + (["--reduced"] if reduced else []), service=svc)
    if rc != 0:
        raise PhaseFailed(f"serve exit code {rc}")

    tel = svc.telemetry()
    # the kernels run inside jitted programs, where the execute wrapper
    # records nothing: count the call sites that dispatch resolved instead
    requests = svc.metrics.snapshot()["counters"]
    kernels = {}
    for kernel in ("flash_attention", "decode_attention"):
        impls = sorted({str(e["config"].get("impl")) for e in tel["executables"]
                        if e["kernel"] == kernel})
        calls = int(sum(c["value"] for c in requests
                        if c["name"] == "dispatch_requests_total"
                        and c["labels"].get("kernel") == kernel))
        kernels[kernel] = {"impl": impls, "calls": calls}
        if impls != ["pallas"] or calls == 0:
            raise PhaseFailed(f"{kernel} ran as {impls} ({calls} dispatched "
                              "call(s)), not the Pallas kernel")

    cfg = get_reduced(ARCH) if reduced else get_config(ARCH)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    prompt = jax.random.randint(jax.random.PRNGKey(seed + 7),
                                (batch, prompt_len), 0, cfg.vocab_size)
    logits, cache = prefill(params, {"tokens": prompt}, cfg,
                            prompt_len + gen, service=svc)
    tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(prompt.dtype)[:, None]
    _, got, _ = make_serve_step(cfg, service=svc)(params, cache, tok, prompt_len)
    _, want, _ = jax.jit(make_serve_step(cfg))(params, cache, tok, prompt_len)
    err = _rel_err(got, want)
    if not err <= LOGITS_TOL:
        raise PhaseFailed(f"first decode step logits differ from the "
                          f"service-free path: {err} > {LOGITS_TOL}")
    counters = {k: v for k, v in tel.items() if isinstance(v, int)}
    return {"kernels": kernels, "logits_rel_err": err,
            "tolerance": LOGITS_TOL, "dispatch": counters}


def phase_training(*, reduced: bool = False, steps: int = 3, batch: int = 4,
                   seq: int = 128) -> dict:
    """A few training steps; the train CLI exits non-zero on a non-finite
    loss."""
    from repro.launch import train

    argv = ["--arch", ARCH, "--steps", str(steps), "--batch", str(batch),
            "--seq", str(seq), "--log-every", "1"]
    rc = train.main(argv + (["--reduced"] if reduced else []))
    if rc != 0:
        raise PhaseFailed(f"train exit code {rc}")
    return {"steps": steps, "batch": batch, "seq": seq}


PHASES = (("paper_loop", phase_autotune), ("serving", phase_serving),
          ("training", phase_training))


def main() -> int:
    try:
        from repro.launch.device import device_info, enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev}", file=sys.stderr)
        return 1
    print(f"[chip_smoke] device {dev}, compile cache {enable_compile_cache()}")

    failures = []
    for name, phase in PHASES:
        t0 = time.perf_counter()
        try:
            summary = phase()
        except Exception as e:  # noqa: BLE001 — report it, run the rest
            failures.append(name)
            traceback.print_exc()
            print(f"[chip_smoke] {name} FAILED after "
                  f"{time.perf_counter() - t0:.1f} s: {type(e).__name__}: {e}",
                  file=sys.stderr)
            continue
        print(f"[chip_smoke] {name} passed in {time.perf_counter() - t0:.1f} s "
              f"(host clock, compiles included): {json.dumps(summary, default=str)}",
              flush=True)
    if failures:
        print(f"chip_smoke: failed phases {failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
