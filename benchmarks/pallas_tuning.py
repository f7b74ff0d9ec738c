"""TPU-target Pallas schedule tuning (backend B2): autotune each kernel's
BlockSpec geometry against the analytic v5e cost model, at the paper's
LARGE dataset sizes. The chosen config is then validated for correctness in
interpret mode at reduced size — schedule legality is by construction, so
the reduced-size check is a full proxy.

Rows report modeled microseconds on TPU v5e for (default MXU tiles) vs
(autotuned), plus the modeled roofline utilization of the tuned schedule.

Shape tables live in :mod:`repro.kernels.problems` (shared with the autotune
CLI and the cost-backend background tuner). Campaign results route through
``repro.dispatch``: pass a :class:`~repro.dispatch.TuningStore` (or a path)
to :func:`tune_all` and each kernel's campaign (a) warm-starts from the
store's nearest tuned records and (b) publishes its winner back, so
successive benchmark runs converge in a fraction of the evaluation budget
and serving picks the configs up for free.
"""

from __future__ import annotations

from benchmarks.common import EVALS
from repro.core import autotune
from repro.dispatch import TuningRecord, TuningStore
from repro.dispatch.lookup import warm_start_material
from repro.kernels.cost import kernel_cost
from repro.kernels.problems import (
    DEFAULTS_TPU,
    LARGE_SHAPES,
    make_cost_evaluator,
    problem_signature_for,
)
from repro.kernels.spaces import kernel_space
from repro.perf.roofline import HW

# back-compat alias: this module's historical evaluator-factory name
make_evaluator = make_cost_evaluator


def _signature(name: str):
    return problem_signature_for(name, backend="cost")


def tune_all(max_evals: int | None = None, store: TuningStore | str | None = None,
             parallel: int = 1):
    if isinstance(store, str):
        store = TuningStore(store)
    rows = []
    for name in LARGE_SHAPES:
        ev = make_cost_evaluator(name)
        base_t, base_info = kernel_cost(name, DEFAULTS_TPU[name], *LARGE_SHAPES[name])
        warm_cfgs, warm_recs = None, None
        if store is not None:
            warm_cfgs, warm_recs = warm_start_material(
                store, name, _signature(name), backend="cost")
        res = autotune(kernel_space(name, target="tpu"), ev,
                       max_evals=max_evals or max(EVALS, 40), learner="RF",
                       seed=1234, parallel=parallel, warm_start=warm_cfgs,
                       warm_start_records=warm_recs)
        b = res.best
        if store is not None and b is not None:
            store.put(TuningRecord(
                kernel=name, signature=_signature(name), backend="cost",
                config=dict(b.config), objective=float(b.objective),
                n_evals=len(res.db), source="benchmark:pallas_tuning"))
        flops = b.info.get("flops", 0.0)
        util = flops / (b.objective * HW.peak_flops) if b.objective > 0 else 0.0
        rows.append((f"pallas_tpu/{name}/default", base_t * 1e6,
                     f"config={DEFAULTS_TPU[name]}"))
        rows.append((f"pallas_tpu/{name}/autotuned", b.objective * 1e6,
                     f"at_eval={b.index};mxu_util={util:.2f};config={b.config}"))
    return rows


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache

    enable_compile_cache()
    from benchmarks.common import emit
    emit(tune_all())
