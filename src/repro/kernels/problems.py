"""Canonical bench/LARGE problem registry + the cost-backend evaluators.

One table of problem dimensions per kernel, shared by the autotune CLI
(``repro.launch.autotune``), the pallas-tuning benchmark
(``benchmarks.pallas_tuning``), and the cost-backend background tuner —
previously the CLI's ``BENCH_PROBLEMS``/``BENCH_DIMS`` and the benchmark's
shape tables drifted independently.

  * ``BENCH_DIMS`` — host-timeable sizes (backend B1, the paper's Core-i7
    role): small enough that one evaluation is milliseconds on CPU.
  * ``LARGE_SHAPES`` — the paper's LARGE dataset sizes: what the analytic
    TPU cost model scores (backend B2) and what a campaign times on a TPU
    (:func:`tpu_problem`); the model kernels use a 16-head 4k-context
    serving shape as their LARGE analog.
  * ``DEFAULTS_TPU`` — the MXU-default schedules the benchmark compares
    autotuned configs against.

The cost-backend half closes the "background tuning on the cost backend"
loop: :func:`make_cost_evaluator` scores configs with
:func:`repro.kernels.cost.kernel_cost` at fixed dims, and
:func:`register_cost_backend` re-registers every costed kernel with a
``VariantSpec.make_evaluator`` that derives the dims from the campaign's
runtime argument shapes — so a :class:`~repro.dispatch.BackgroundTuner`
attached to a TPU-target :class:`~repro.dispatch.DispatchService` tunes
schedules analytically on a host with no TPU attached.
"""

from __future__ import annotations

from typing import Callable, Mapping

import numpy as np

from repro.core.plopper import EvalResult

__all__ = [
    "BENCH_DIMS",
    "BENCH_PROBLEMS",
    "DEFAULTS_TPU",
    "LARGE_SHAPES",
    "MIXTRAL_GMM_SHAPES",
    "PROXY_DIMS",
    "bench_problem",
    "campaign_dims",
    "dims_from_signature",
    "fidelity_ready",
    "fidelity_readiness",
    "make_cost_evaluator",
    "problem_signature_for",
    "register_cost_backend",
    "tpu_problem",
]

# host-timeable problem dims behind the bench problems (heat3d includes its
# tsteps knob); the per-kernel dim order matches kernels.ref init_* functions
BENCH_DIMS = {
    "syr2k": (240, 200),
    "mm3": (200, 180, 160, 150, 170),
    "lu": (256,),
    "heat3d": (40, 8),
    "covariance": (300, 240),
    "floyd_warshall": (240,),
    "flash_attention": (4, 128, 128, 64),
    "decode_attention": (8, 2, 128, 64),   # (BH, G, seq_bucket, hd)
    "matmul": (256, 192, 224),
    "grouped_matmul": (64, 256, 384, 8),   # (rows, k, n, groups)
}

# the paper's LARGE dataset sizes per kernel; the model kernels (serving hot
# path) use a 16-head 4k-context serving shape as their "LARGE" analog
LARGE_SHAPES = {
    "syr2k": (1200, 1000),
    "mm3": (800, 900, 1000, 1100, 1200),
    "lu": (2000,),
    "heat3d": (120, 500),
    "covariance": (1400, 1200),
    "floyd_warshall": (2800,),
    "flash_attention": (16, 4096, 4096, 128),
    "decode_attention": (16, 8, 4096, 128),
    "matmul": (2000, 2300, 2600),
    "grouped_matmul": (64, 4096, 14336, 8),
}

# the expert layer's gate/up grouped matmul in the mixtral-8x7b serving cell
# (bench/configs/mixtral-8x7b.json): a decode step of 32 sequences routes
# 64 rows over the 8 experts; a 128-token prompt's forward routes 256. The
# kernel takes the 4 layers' stacks whole, as 32 groups of which one
# layer's 8 hold rows (models/moe.py).
MIXTRAL_GMM_SHAPES = {
    "decode": (64, 4096, 14336, 32),
    "prefill": (256, 4096, 14336, 32),
}

# reduced-shape proxy sizes (repro.fidelity rung 1): the same kernels at
# roughly half the linear problem dims (an eighth of the work for the cubic
# kernels), so a proxy evaluation costs a fraction of the full bench timing
# while preserving the schedule landscape's ordering well enough to screen.
# heat3d additionally cuts tsteps (a pure multiplier on config ranking).
PROXY_DIMS = {
    "syr2k": (120, 100),
    "mm3": (100, 90, 80, 75, 85),
    "lu": (128,),
    "heat3d": (24, 4),
    "covariance": (150, 120),
    "floyd_warshall": (120,),
    "flash_attention": (2, 64, 64, 64),
    "decode_attention": (4, 2, 64, 64),
    "matmul": (128, 96, 112),
    "grouped_matmul": (32, 128, 256, 8),
}

DEFAULTS_TPU = {
    "syr2k": dict(bi=128, bj=128, bk=128),
    "mm3": dict(bm=128, bn=128, bk=128),
    "lu": dict(bs=32, bm=128, bn=128),
    "heat3d": dict(bi=8, fuse_t=1),
    "covariance": dict(bi=128, bj=128, bk=256),
    "floyd_warshall": dict(bs=64, bi=128, bj=128, unroll=1),
    "flash_attention": dict(impl="pallas", bq=128, bk=128),
    "decode_attention": dict(impl="pallas", bk=128, hg=1, page=128),
    "matmul": dict(bm=128, bn=128, bk=128, pack=True),
    "grouped_matmul": dict(impl="pallas", tm=128, tk=1024, tn=2048),
}


def bench_problem(name: str, dims: tuple | None = None):
    """Variant factory for ``name`` — the thing a
    :class:`~repro.core.plopper.TimingEvaluator` wall-clocks (backend B1).
    Defaults to :data:`BENCH_DIMS` sizes; pass ``dims`` explicitly (e.g.
    :data:`PROXY_DIMS`) for the fidelity ladder's reduced-shape proxy rung."""
    from repro.kernels import model_kernels as MK
    from repro.kernels import ref as R
    from repro.kernels import variants as V

    dims = BENCH_DIMS[name] if dims is None else tuple(dims)
    if name == "heat3d":
        return V.heat3d_host(R.init_heat3d(dims[0]), tsteps=dims[1])
    if name == "flash_attention":
        return MK.flash_attention_host(MK.init_flash_attention(*dims))
    if name == "decode_attention":
        return MK.decode_attention_host(MK.init_decode_attention(*dims))
    if name == "matmul":
        return MK.matmul_host(MK.init_matmul(*dims))
    if name == "grouped_matmul":
        return MK.grouped_matmul_host(MK.init_grouped_matmul(*dims))
    init = getattr(R, f"init_{name}")
    host = getattr(V, f"{name}_host")
    return host(init(*dims))


# name -> thunk returning that kernel's variant factory; the registry form of
# :func:`bench_problem` for callers that iterate the bench suite
BENCH_PROBLEMS = {name: (lambda n=name: bench_problem(n)) for name in BENCH_DIMS}


def tpu_problem(name: str, dims: tuple | None = None):
    """Variant factory for ``name`` on a TPU: the Pallas op of
    :mod:`repro.kernels.ops` (the model kernels' dispatch builders for the
    attention kernels) at the paper's :data:`LARGE_SHAPES`, or at ``dims``.
    The op runs in interpret mode off a TPU, which only tests want."""
    import functools

    import jax.numpy as jnp

    from repro.kernels import model_kernels as MK
    from repro.kernels import ops
    from repro.kernels import ref as R

    dims = LARGE_SHAPES[name] if dims is None else tuple(dims)
    if name == "flash_attention":
        return MK.flash_attention_host(MK.init_flash_attention(*dims))
    if name == "decode_attention":
        return MK.decode_attention_host(MK.init_decode_attention(*dims))
    if name == "grouped_matmul":
        return MK.grouped_matmul_host(MK.init_grouped_matmul(*dims, dtype=jnp.bfloat16))
    if name == "matmul":
        op, args = ops.matmul_op, MK.init_matmul(*dims)
    elif name == "heat3d":
        op = functools.partial(ops.heat3d_op, tsteps=dims[1])
        args = R.init_heat3d(dims[0])
    else:
        op, args = getattr(ops, f"{name}_op"), getattr(R, f"init_{name}")(*dims)

    def factory(cfg):
        return functools.partial(op, config=dict(cfg)), args

    return factory


def campaign_dims(kernel: str, backend: str) -> tuple:
    """Problem dims a campaign on ``backend`` runs at: the paper's
    :data:`LARGE_SHAPES` for the cost model and for timings on a TPU,
    :data:`BENCH_DIMS` for timings on a host."""
    from repro.kernels.util import default_target

    if backend == "cost" or default_target() == "tpu":
        return LARGE_SHAPES[kernel]
    return BENCH_DIMS[kernel]


def problem_signature_for(kernel: str, backend: str):
    """Per-argument store signature for a kernel's canonical problem — the
    same scheme ``repro.dispatch`` derives from runtime args, so configs
    published offline resolve at ``dispatch()`` time. The dims are
    :func:`campaign_dims`."""
    from repro.kernels.ref import problem_signature

    return problem_signature(kernel, *campaign_dims(kernel, backend))


def dims_from_signature(kernel: str, signature) -> tuple:
    """Inverse of :func:`repro.kernels.ref.problem_signature`: recover the
    problem dims from a (possibly runtime-derived) shape signature. Trailing
    static-kwarg entries (e.g. flash attention's folded ``causal`` flag) are
    ignored."""
    if kernel == "syr2k":
        return (signature[0][0], signature[1][1])
    if kernel == "mm3":
        (P, Q), (_, R_), (_, S), (_, T) = signature[:4]
        return (P, Q, R_, S, T)
    if kernel == "lu":
        return (signature[0][0],)
    if kernel == "heat3d":
        # tsteps rides in as a static-kwarg entry when present (dispatch folds
        # it into the runtime signature); a bare-array signature — e.g. a
        # background factory whose args are just the grid — scores one step,
        # which preserves config ranking (tsteps is a pure multiplier)
        t = signature[1][0] if len(signature) > 1 and len(signature[1]) == 1 else 1
        return (signature[0][0], t)
    if kernel == "covariance":
        return tuple(signature[0])
    if kernel == "floyd_warshall":
        return (signature[0][0],)
    if kernel == "flash_attention":
        (BH, Sq, hd), (_, Sk, _) = signature[0], signature[1]
        return (BH, Sq, Sk, hd)
    if kernel == "decode_attention":
        (BH, G, hd), (_, S, _) = signature[0], signature[1]
        return (BH, G, S, hd)
    if kernel == "matmul":
        (M, K), (_, N) = signature[0], signature[1]
        return (M, K, N)
    if kernel == "grouped_matmul":
        (m, k), (g, _, n) = signature[0], signature[1]
        return (m, k, n, g)
    raise KeyError(f"unknown kernel {kernel!r}")


def fidelity_ready(kernel: str) -> bool:
    """True when ``kernel`` can participate in the fidelity ladder's rung 0:
    an analytic cost-model entry exists to screen with. Kernels without one
    can still cascade over the timing rungs, but pay hardware (or proxy
    hardware) for every screen."""
    from repro.kernels.cost import KERNEL_COST_FNS

    return kernel in KERNEL_COST_FNS


def fidelity_readiness() -> dict[str, bool]:
    """``kernel -> fidelity_ready`` over every dispatch-registered kernel —
    the machine-readable coverage map ``repro-analyze space`` publishes, so a
    kernel registered for dispatch but missing a cost-model entry (and thus
    unable to join rung 0) is a reviewable fact rather than a silent gap."""
    from repro.dispatch.registry import registered

    return {name: fidelity_ready(name) for name in registered()}


def make_cost_evaluator(kernel: str, dims: tuple | None = None) -> Callable:
    """``config -> EvalResult`` scored by the analytic TPU cost model at
    ``dims`` (default: the paper's LARGE sizes). Infeasible configs (VMEM
    over budget) come back failed with the model's penalty semantics."""
    from repro.kernels.cost import kernel_cost

    shape = tuple(dims) if dims is not None else LARGE_SHAPES[kernel]

    def evaluate(cfg: Mapping) -> EvalResult:
        t, info = kernel_cost(kernel, cfg, *shape)
        if not np.isfinite(t):
            return EvalResult(1e9, False, info)
        return EvalResult(t, True, info)

    return evaluate


def _cost_make_evaluator(kernel: str) -> Callable:
    """A ``VariantSpec.make_evaluator``: given a background campaign's
    ``factory(config) -> (fn, args)``, return an evaluator that never runs
    ``fn`` — it derives the problem dims from the args' shapes and scores the
    config analytically. Thread-safe and hardware-free by construction."""

    def make(factory: Callable) -> Callable:
        inner: list[Callable] = []  # built once, after dims are derived

        def evaluate(cfg: Mapping) -> EvalResult:
            if not inner:
                _, args = factory(cfg)
                sig = tuple(tuple(int(d) for d in np.shape(a)) for a in args)
                inner.append(make_cost_evaluator(kernel, dims_from_signature(kernel, sig)))
            return inner[0](cfg)

        return evaluate

    return make


def register_cost_backend() -> None:
    """Re-register every costed kernel into the dispatch registry with the
    roofline cost model as its background-campaign evaluator. Call this on a
    TPU-target host before attaching a :class:`~repro.dispatch.BackgroundTuner`
    to a ``DispatchService(backend="cost", target="tpu")`` — campaigns then
    tune BlockSpec geometry against the analytic model instead of
    wall-clocking XLA-on-host, which is meaningless for a TPU target."""
    import functools

    from repro.dispatch.registry import get, register
    from repro.kernels.cost import KERNEL_COST_FNS
    from repro.kernels.spaces import kernel_space

    for name in KERNEL_COST_FNS:
        spec = get(name)  # loads builtins; preserves each kernel's builder
        register(name, spec.builder,
                 space=functools.partial(kernel_space, name),
                 make_evaluator=_cost_make_evaluator(name))
