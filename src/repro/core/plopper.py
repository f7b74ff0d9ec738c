"""Plopper: turn a configuration into a measurable program and score it.

In the paper the plopper substitutes ``#P0..#Pm`` into a code mold, invokes
``clang`` and runs the binary (exe.pl). Here the "mold" is a *variant factory*
— a Python callable ``factory(config) -> (fn, args)`` that closes over the
configuration to build a concrete JAX program — and two evaluation backends
replace compile-and-run:

  * :class:`TimingEvaluator` (backend B1) — jit, warm up, and wall-clock the
    variant on this host. This is exactly the role the paper's Core-i7 plays.
  * :class:`CostModelEvaluator` (backend B2) — ``.lower().compile()`` the
    variant for the TPU-target mesh and score it with the three-term roofline
    model (compute / memory / collective seconds from the compiled HLO). Used
    where no hardware exists to time (the whole point of a structural model).

Both catch per-candidate failures and return a penalty instead of raising:
one broken configuration must not kill a 200-evaluation campaign. That is the
fault-tolerance contract the search loop relies on.
"""

from __future__ import annotations

import dataclasses
import time
import traceback
from typing import Any, Callable, Mapping

import jax

__all__ = [
    "EvalResult",
    "TimingEvaluator",
    "CostModelEvaluator",
    "DeadlineEvaluator",
    "PENALTY",
]

PENALTY = float(1.0e9)


@dataclasses.dataclass
class EvalResult:
    objective: float
    ok: bool
    info: dict


class TimingEvaluator:
    """Backend B1: measured wall-clock of the jitted variant on this host.

    ``factory(config)`` must return ``(fn, args)``; ``fn(*args)`` is jitted
    and compiled (``info["compile_sec"]``), warmed up ``warmup`` times, then
    timed ``repeats`` times; the *minimum* is reported (the paper reports the
    smallest execution time of repeated runs).
    """

    def __init__(self, factory: Callable[[Mapping[str, Any]], tuple], repeats: int = 3,
                 warmup: int = 1, penalty: float = PENALTY, jit: bool = True):
        self.factory = factory
        self.repeats = repeats
        self.warmup = warmup
        self.penalty = penalty
        self.jit = jit

    def __call__(self, config: Mapping[str, Any]) -> EvalResult:
        try:
            fn, args = self.factory(config)
            info = {}
            run = fn
            if self.jit:
                t0 = time.perf_counter()
                run = jax.jit(fn).lower(*args).compile()
                info["compile_sec"] = time.perf_counter() - t0
            out = None
            for _ in range(self.warmup):
                out = run(*args)
            jax.block_until_ready(out)
            times = []
            for _ in range(self.repeats):
                t0 = time.perf_counter()
                out = run(*args)
                jax.block_until_ready(out)
                times.append(time.perf_counter() - t0)
            return EvalResult(min(times), True, dict(info, times_sec=times))
        except Exception as e:  # noqa: BLE001 — any failure becomes a penalty
            return EvalResult(
                self.penalty, False,
                {"error": f"{type(e).__name__}: {e}", "trace": traceback.format_exc(limit=3)},
            )


class CostModelEvaluator:
    """Backend B2: structural roofline score of the compiled TPU-target program.

    ``factory(config)`` must return a *thunk* producing a
    ``jax.stages.Lowered`` (so compilation happens inside the failure guard).
    ``score(lowered) -> (seconds, info)`` defaults to the repo's three-term
    roofline (see repro.perf.roofline); injectable for tests.
    """

    def __init__(self, factory: Callable[[Mapping[str, Any]], Callable[[], Any]],
                 score: Callable[[Any], tuple[float, dict]] | None = None,
                 penalty: float = PENALTY):
        if score is None:
            from repro.perf.roofline import score_lowered  # lazy: avoids cycle
            score = score_lowered
        self.factory = factory
        self.score = score
        self.penalty = penalty

    def __call__(self, config: Mapping[str, Any]) -> EvalResult:
        try:
            thunk = self.factory(config)
            lowered = thunk()
            seconds, info = self.score(lowered)
            return EvalResult(float(seconds), True, info)
        except Exception as e:  # noqa: BLE001
            return EvalResult(
                self.penalty, False,
                {"error": f"{type(e).__name__}: {e}", "trace": traceback.format_exc(limit=3)},
            )


class DeadlineEvaluator:
    """Straggler mitigation for evaluation campaigns: give up on a candidate
    whose evaluation exceeds ``deadline_sec`` and penalize it.

    Wall-clock is checked *after* the inner call returns (JAX work is not
    preemptible from Python), so the deadline converts stragglers into
    penalized records rather than hung campaigns on *subsequent* candidates:
    any candidate observed to exceed the deadline is recorded as failed, and
    the measured time still feeds the DB so findMin never selects it.
    """

    def __init__(self, inner: Callable[[Mapping[str, Any]], EvalResult], deadline_sec: float):
        self.inner = inner
        self.deadline_sec = deadline_sec

    def __call__(self, config: Mapping[str, Any]) -> EvalResult:
        t0 = time.perf_counter()
        res = self.inner(config)
        wall = time.perf_counter() - t0
        if wall > self.deadline_sec:
            info = dict(res.info)
            info["straggler_wall_sec"] = wall
            return EvalResult(max(res.objective, self.inner_penalty()), False, info)
        return res

    def inner_penalty(self) -> float:
        return getattr(self.inner, "penalty", PENALTY)
