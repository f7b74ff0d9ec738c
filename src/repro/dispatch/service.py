"""The runtime dispatch service: ``dispatch(kernel_name, *args)``.

Resolution pipeline per call:

  1. derive the shape signature from the runtime args (plus static kwargs);
  2. consult the in-process **compiled-executable cache** keyed by
     ``(kernel, config, signature)`` — a signature-keyed fast map (TTL
     ``resolve_ttl_sec``) remembers the last resolution, so a hit returns
     the already-jitted variant with zero store traffic; the TTL bounds how
     long a cross-process store improvement can go unnoticed, and in-process
     improvements are picked up immediately via :meth:`invalidate`;
  3. on a cache miss, resolve a config from the :class:`TuningStore`
     (exact hit → nearest neighbor → registered space default), build the
     variant via the dispatch registry, jit it, and cache it;
  4. when the resolution is a miss, a too-distant neighbor, or a stale
     record — and a :class:`~repro.dispatch.background.BackgroundTuner` is
     attached — enqueue an async BO campaign for this exact signature. Its
     result is published to the store and hot-swapped in by invalidating
     the affected executable-cache entries, so later calls pick it up.

``stats`` counts every path (store_exact / store_near / store_default,
exec_hit / exec_miss, bg_enqueued) so serving dashboards can watch cache
efficiency and tuning pressure.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable

import jax

from repro.analyze.feasibility import check_config
from repro.core.space import config_key
from repro.engine.executors import evaluator_for_spec
from repro.dispatch.lookup import Resolution, resolve
from repro.dispatch.registry import get as get_variant
from repro.dispatch.signature import shape_signature, signature_key
from repro.dispatch.store import TuningStore
from repro.guard.faults import fault_point
from repro.kernels.util import default_target
from repro.obs.metrics import get_registry, summarize_histograms
from repro.obs.trace import get_tracer, install_jax_hooks

__all__ = ["DispatchService", "dispatch", "call", "get_service", "configure"]


class DispatchService:
    def __init__(
        self,
        store: TuningStore | None = None,
        *,
        backend: str = "host",
        target: str | None = None,
        distance_threshold: float = 1.0,
        staleness_sec: float | None = None,
        tuner: Any | None = None,
        jit: bool = True,
        resolve_ttl_sec: float = 30.0,
        fast_sweep_size: int = 256,
        metrics=None,
    ):
        self.store = store
        install_jax_hooks()   # compile events land under the span that caused them
        # repro.obs registry: per-signature execute-latency histograms and
        # request counters. Recording is shard-local (lock-free), so the
        # fast-hit path's one-lock contract holds with metrics enabled.
        self.metrics = metrics if metrics is not None else get_registry()
        self.backend = backend
        # tile spaces and defaults follow the device: on a TPU the space
        # defaults pick the Pallas kernels, on a host the XLA fallbacks
        self.target = target if target is not None else default_target()
        self.distance_threshold = distance_threshold
        self.staleness_sec = staleness_sec
        self.tuner = tuner
        self.jit = jit
        self.resolve_ttl_sec = resolve_ttl_sec
        self.fast_sweep_size = fast_sweep_size
        # signature -> (exec key, monotonic expiry): lets repeat dispatches
        # skip store refresh + nearest-neighbor scan on the hot path
        self._fast: dict[tuple, tuple[tuple, float]] = {}
        # build_failed counts configs that died in the builder/eval_shape;
        # infeasible counts configs the static feasibility pass
        # (repro.analyze) rejected BEFORE any build was attempted — the two
        # were one stat before the analyze subsystem split them
        self.stats = {
            "store_exact": 0, "store_near": 0, "store_default": 0,
            "exec_hit": 0, "exec_miss": 0, "bg_enqueued": 0, "build_failed": 0,
            "infeasible": 0,
            "serve_rebuilt": 0, "sync_applied": 0, "sync_published": 0,
        }
        self._sync = None  # repro.fleet.SyncAgent, via attach_sync()
        self._kv_cache = None  # serve.PagedKVCache, via attach_kv_cache()
        self._guard = None  # repro.guard.GuardAgent, via attach_guard()
        # retune material per (kernel, sig_key): (signature, static items,
        # arg shape/dtype structs) captured on the miss path so the drift
        # watcher can re-campaign a signature without live args in hand
        self._retune: dict[tuple, tuple] = {}
        self._exec: dict[tuple, Callable] = {}
        # jit_cached sources + stable per-name proxies: invalidate() drops the
        # compiled entry, and the proxy (which callers hold) lazily re-jits
        # from the source — the cross-service serve-step hot swap
        self._fn_src: dict[tuple, Callable] = {}
        self._fn_proxy: dict[tuple, Callable] = {}
        self._lock = threading.RLock()

    # -- config resolution -------------------------------------------------------

    def _resolve_nostats(self, kernel: str, signature):
        """Store resolution without touching stats or the lock; returns
        ``(config, resolution, stat_name)`` so the caller can fold the stat
        bump into whatever critical section it is already paying for."""
        res = None
        if self.store is not None:
            self.store.refresh()
            res = resolve(self.store, kernel, signature, self.backend)
        if res is None:
            return get_variant(kernel).default_config(self.target), None, "store_default"
        return dict(res.config), res, "store_exact" if res.exact else "store_near"

    def resolve_config(self, kernel: str, signature) -> tuple[dict, Resolution | None]:
        """Store-resolved config for a signature, falling back to the
        registered space default when the store is empty/absent."""
        config, res, stat = self._resolve_nostats(kernel, signature)
        with self._lock:
            self.stats[stat] += 1
        return config, res

    def _needs_tuning(self, res: Resolution | None) -> bool:
        if res is None:
            return True
        if not res.exact and res.distance > self.distance_threshold:
            return True
        if self.staleness_sec is not None and res.record.age_sec() > self.staleness_sec:
            return True
        return False

    # -- the runtime API ---------------------------------------------------------

    def dispatch(self, kernel: str, *args, **static_kw) -> Callable:
        """Return a jitted variant of ``kernel`` tuned for these args' shapes.
        The returned callable takes the same positional args."""
        spec = get_variant(kernel)
        sig = shape_signature(list(args) + [v for _, v in sorted(static_kw.items())])
        static_id = tuple(sorted(static_kw.items()))
        sig_key = signature_key(sig)
        fast_key = (kernel, sig_key, static_id)
        now = time.monotonic()
        # hot path: ONE lock acquisition — fast-map read, executable lookup,
        # and the hit-stat bump share a single critical section (the metric
        # bump is shard-local and takes no lock)
        with self._lock:
            entry = self._fast.get(fast_key)
            if entry is not None:
                exec_key, expires = entry
                fn = self._exec.get(exec_key)
                if fn is not None and now < expires:
                    self.stats["exec_hit"] += 1
                    self.metrics.add("dispatch_requests_total",
                                     kernel=kernel, path="fast_hit")
                    return fn
                del self._fast[fast_key]  # expired or orphaned: don't leak
        # miss path: resolve outside the lock (store refresh does file I/O),
        # then fold the resolve stat and the executable-cache probe into one
        # critical section
        tracer = get_tracer()
        t0 = time.perf_counter()
        with tracer.span("dispatch.lookup", kernel=kernel, signature=sig_key):
            config, res, resolve_stat = self._resolve_nostats(kernel, sig)
        self.metrics.observe("dispatch_lookup_seconds",
                             time.perf_counter() - t0, kernel=kernel)
        self.metrics.add("dispatch_requests_total", kernel=kernel,
                         path=resolve_stat)
        key = fast_key + (config_key(config),)
        with self._lock:
            self.stats[resolve_stat] += 1
            fn = self._exec.get(key)
            self.stats["exec_hit" if fn is not None else "exec_miss"] += 1
        built = None
        if fn is None and res is not None:
            # statically-infeasible store records never cost a build or an
            # eval_shape: the feasibility pass proves from the config and
            # the signature's problem dims alone that the builder would die
            # (missing params, non-positive tiles, VMEM over budget, ...).
            # Exact hits are quarantined with the machine-readable reason
            # codes; near neighbors just degrade (same asymmetry as the
            # runtime build_failed path below).
            verdict = check_config(kernel, config, signature=sig,
                                   target=self.target)
            if not verdict.ok:
                if self.store is not None and res.exact:
                    self.store.quarantine(res.record, reason=verdict.reason())
                res = None
                config = spec.default_config(self.target)
                key = fast_key + (config_key(config),)
                with self._lock:
                    self.stats["infeasible"] += 1
                    fn = self._exec.get(key)  # default may already be compiled
                self.metrics.add("dispatch_requests_total", kernel=kernel,
                                 path="infeasible")
        if fn is None and res is not None:
            # a store-resolved config is untrusted input to the serving path:
            # validate build + abstract trace now, so a poisoned record
            # degrades to the default config instead of raising at the caller
            try:
                with tracer.span("dispatch.build", kernel=kernel,
                                 signature=sig_key):
                    built = spec.builder(config, **static_kw)
                    if args:
                        jax.eval_shape(built, *args)
            except Exception:
                # only an exact hit proves the record is bad for its own
                # signature; a nearest neighbor may merely not transfer to
                # this shape (e.g. an indivisible block), and quarantining it
                # would destroy a config that is valid where it was tuned
                if self.store is not None and res.exact:
                    self.store.quarantine(res.record, reason="build_failed")
                built, res = None, None
                config = spec.default_config(self.target)
                key = fast_key + (config_key(config),)
                with self._lock:
                    self.stats["build_failed"] += 1
                    fn = self._exec.get(key)  # default may already be compiled
                self.metrics.add("dispatch_requests_total", kernel=kernel,
                                 path="build_failed")
        if fn is None:
            if built is None:
                with tracer.span("dispatch.build", kernel=kernel,
                                 signature=sig_key):
                    built = spec.builder(config, **static_kw)
            fn = jax.jit(built) if self.jit else built
            # the cached executable is the instrumented wrapper, so repeat
            # dispatches return the identical object and every execution
            # lands in the per-signature latency histogram
            fn = self._instrument_execute(fn, kernel, sig_key, sig=sig,
                                          config=config, static_kw=static_kw)
        retune_material = None
        if self._guard is not None:
            # shape/dtype structs, not live arrays: enough to synthesize
            # arguments for a drift-triggered re-campaign, without pinning
            # serving buffers in this map
            retune_material = (sig, static_id, tuple(
                jax.ShapeDtypeStruct(a.shape, a.dtype)
                if hasattr(a, "shape") else a for a in args))
        # publish: executable insert, fast-map store, and the TTL sweep share
        # the final critical section
        with self._lock:
            fn = self._exec.setdefault(key, fn)
            self._fast[fast_key] = (key, time.monotonic() + self.resolve_ttl_sec)
            if retune_material is not None:
                self._retune[(kernel, sig_key)] = retune_material
            if len(self._fast) > self.fast_sweep_size:
                self._sweep_fast_locked(time.monotonic())
        if self.tuner is not None and self.store is not None and self._needs_tuning(res):
            self._enqueue_tuning(spec, kernel, sig, args, static_kw)
        return fn

    def call(self, kernel: str, *args, **static_kw):
        """Resolve, build, and run in one step."""
        return self.dispatch(kernel, *args, **static_kw)(*args)

    def _instrument_execute(self, fn: Callable, kernel: str, sig_key: str,
                            *, sig=None, config=None,
                            static_kw=None) -> Callable:
        """Wrap an executable so every eager call records into the
        per-signature execute-latency histogram and a ``dispatch.execute``
        span. The wrapper is what the executable cache stores, so the
        identity contract (repeat dispatch returns the same object) is
        unchanged.

        A call under a jit trace (a kernel inside the serve step or the
        prefill scans) records neither: its Python seconds are trace time,
        and the kernel's device time is in the profiler's trace under the
        kernel's name.

        On asynchronous backends this times dispatch-to-return as the caller
        observes it — the same quantity a serving loop's own latency sees;
        it does not force a ``block_until_ready`` sync, which would
        serialize the pipeline it is measuring. The exception is a
        shadow-sampled call (epsilon fraction, attached guard only): there
        the wrapper synchronizes to obtain a true wall time and tells it
        into the tuning store."""
        metrics, backend = self.metrics, self.backend

        def timed(*a, **kw):
            if any(isinstance(x, jax.core.Tracer) for x in a):
                return fn(*a, **kw)
            guard = self._guard
            mode = (guard.shadow_mode(kernel, sig_key)
                    if guard is not None else None)
            t0 = time.perf_counter()
            try:
                fault_point("dispatch.latency", kernel=kernel,
                            signature=sig_key)
                with get_tracer().span("dispatch.execute", kernel=kernel,
                                       signature=sig_key):
                    out = fn(*a, **kw)
                if mode is not None:
                    jax.block_until_ready(out)
                    guard.on_shadow(kernel, sig, config, static_kw, a,
                                    time.perf_counter() - t0, mode)
                return out
            finally:
                metrics.observe("dispatch_execute_seconds",
                                time.perf_counter() - t0, kernel=kernel,
                                signature=sig_key, backend=backend)

        timed.__wrapped__ = fn
        timed.config = dict(config) if config is not None else None
        return timed

    def _enqueue_tuning(self, spec, kernel, sig, args, static_kw) -> None:
        def factory(cfg):
            return spec.builder(cfg, **static_kw), args

        # make_evaluator override (e.g. the roofline cost backend registered
        # by repro.kernels.problems.register_cost_backend) else wall-clock
        evaluator = evaluator_for_spec(spec, factory)
        fut = self.tuner.submit(
            kernel, sig, self.backend, space=spec.space(self.target),
            evaluator=evaluator, on_done=self._on_tuned)
        if fut is not None:
            with self._lock:
                self.stats["bg_enqueued"] += 1

    def _on_tuned(self, kernel: str, signature, backend: str) -> None:
        self.invalidate(kernel, signature)
        if self._sync is not None:
            # a background campaign just published: push the new config
            # fleet-wide now instead of waiting a full anti-entropy interval
            self._sync.nudge()

    # -- fleet replication (repro.fleet) -----------------------------------------

    def attach_sync(self, agent) -> None:
        """Bind a :class:`repro.fleet.SyncAgent`: replication counters land
        in ``stats`` (``sync_applied`` / ``sync_published``), replication lag
        shows up in :meth:`telemetry`, and local background-tuning publishes
        nudge the agent to push promptly."""
        self._sync = agent
        if self.tuner is not None and getattr(self.tuner, "on_publish", None) is None:
            self.tuner.on_publish = lambda rec: agent.nudge()

    def attach_guard(self, agent) -> None:
        """Bind a :class:`repro.guard.GuardAgent`: the instrumented execute
        wrapper starts shadow-sampling an epsilon fraction of eager calls,
        retune material is captured per signature so the drift watcher can
        re-campaign without live args, and :meth:`telemetry` grows a
        ``guard`` section. Attach before the first dispatch — wrappers
        created earlier keep serving, but their signatures only gain shadow
        sampling after an :meth:`invalidate`."""
        self._guard = agent

    def request_retune(self, kernel: str, sig_key: str) -> bool:
        """Force a background re-campaign for a signature seen earlier by
        :meth:`dispatch` (the drift watcher's recovery path). Returns False
        when no tuner/store is attached or the signature was never served
        with a guard attached."""
        if self.tuner is None or self.store is None:
            return False
        with self._lock:
            material = self._retune.get((kernel, sig_key))
        if material is None:
            return False
        sig, static_id, shapes = material
        spec = get_variant(kernel)
        args = tuple(
            jax.numpy.zeros(s.shape, s.dtype)
            if isinstance(s, jax.ShapeDtypeStruct) else s for s in shapes)
        self._enqueue_tuning(spec, kernel, sig, args, dict(static_id))
        return True

    def attach_kv_cache(self, cache) -> None:
        """Bind a :class:`repro.serve.PagedKVCache`: its paged accounting
        (pages allocated vs tokens resident, occupancy) shows up in
        :meth:`telemetry` under ``kv_cache`` next to the dispatch counters
        the same serving loop produces."""
        self._kv_cache = cache

    def telemetry(self) -> dict:
        """One merged serving-telemetry view: the dispatch counters, the
        background tuner's optimizer-overhead aggregates (ask/tell/wait
        seconds), the sync agent's replication lag (ops pending, last-sync
        age) when one is attached, the attached paged KV cache's
        page/token accounting (under ``kv_cache``), and — under
        ``execute_latency`` — per-signature p50/p99 execute latency from
        the obs registry's histograms, and under ``executables`` the config
        each cached kernel executable was built from (so a caller can see
        which implementation, e.g. ``impl == "pallas"``, served a
        signature). All pre-existing flat keys are unchanged."""
        with self._lock:
            out = dict(self.stats)
            execs = [(k, fn) for k, fn in self._exec.items() if k[0] != "__fn__"]
        out["executables"] = [
            {"kernel": k[0], "signature": k[1], "config": fn.config}
            for k, fn in execs]
        if self.tuner is not None and getattr(self.tuner, "stats", None):
            out.update(self.tuner.stats)
        if self._sync is not None:
            out.update(self._sync.lag())
        if self._kv_cache is not None:
            out["kv_cache"] = self._kv_cache.stats()
        if self._guard is not None:
            out["guard"] = self._guard.summary()
        out["execute_latency"] = [
            {
                "kernel": row["labels"].get("kernel"),
                "signature": row["labels"].get("signature"),
                "backend": row["labels"].get("backend"),
                "count": row["count"],
                "p50_sec": row["p50"],
                "p99_sec": row["p99"],
                "mean_sec": row["sum"] / row["count"] if row["count"] else None,
            }
            for row in summarize_histograms(
                self.metrics.snapshot(), name="dispatch_execute_seconds")
        ]
        return out

    # -- cache management --------------------------------------------------------

    def _sweep_fast_locked(self, now: float) -> int:
        """Drop expired ``_fast`` entries (caller holds the lock). Without
        this, jittery serving shapes grow the TTL map without bound — expiry
        was otherwise only checked on hit."""
        doomed = [k for k, (_, expires) in self._fast.items() if now >= expires]
        for k in doomed:
            del self._fast[k]
        return len(doomed)

    def invalidate(self, kernel: str | None = None, signature=None) -> int:
        """Drop executable-cache entries (all, per kernel, or per kernel+sig)
        so the next dispatch re-resolves — the hot-swap half of background
        tuning. Returns the number of kernel entries dropped.

        ``jit_cached`` serve steps are invalidated alongside: a jitted serve
        step bakes in whatever kernel executables were dispatched at trace
        time, so a config hot swap must also force those steps to re-trace.
        Their compiled entries are dropped (any entry could close over the
        affected kernel) and lazily rebuilt from source on next call through
        the stable proxy callers hold."""
        sig_key = signature_key(signature) if signature is not None else None

        def matches(k):
            return k[0] != "__fn__" and \
                   (kernel is None or k[0] == kernel) and \
                   (sig_key is None or k[1] == sig_key)

        with self._lock:
            doomed = [k for k in self._exec if matches(k)]
            for k in doomed:
                del self._exec[k]
            for k in [k for k in self._fast if matches(k)]:
                del self._fast[k]
            if doomed or kernel is None:
                for k in list(self._fn_src):
                    self._exec.pop(k, None)
            return len(doomed)

    # -- generic executable cache (serving integration) --------------------------

    def jit_cached(self, name: str, fn: Callable, *, span: str | None = None,
                   span_attrs: Callable[..., dict] | None = None) -> Callable:
        """Cache-and-jit an arbitrary callable under a stable name, sharing
        the service's executable cache and hit/miss counters. Used by the
        serving step so repeated ``make_serve_step`` calls for the same model
        reuse one compiled entry point.

        Returns a stable proxy, not the jitted function itself: when
        :meth:`invalidate` drops the compiled entry (a kernel config hot
        swap), every held reference transparently re-traces against the new
        configs on its next call instead of serving stale executables.

        With ``span``, each call through the proxy is a ``repro.obs`` span
        of that name (attributes from ``span_attrs(*args)``): it times the
        launch, and holds any retrace or compile the call causes."""
        key = ("__fn__", name, (), ())
        with self._lock:
            self._fn_src.setdefault(key, fn)
            cached = self._exec.get(key)
            if cached is not None:
                self.stats["exec_hit"] += 1
            else:
                self.stats["exec_miss"] += 1
        if cached is None:
            jitted = jax.jit(fn) if self.jit else fn
            with self._lock:
                self._exec.setdefault(key, jitted)
        with self._lock:
            proxy = self._fn_proxy.get(key)
            if proxy is None:
                proxy = self._fn_proxy[key] = self._make_fn_proxy(
                    key, span, span_attrs)
        return proxy

    def _make_fn_proxy(self, key: tuple, span: str | None = None,
                       span_attrs: Callable[..., dict] | None = None) -> Callable:
        def proxy(*args, **kw):
            if span is None:
                return run(*args, **kw)
            attrs = span_attrs(*args, **kw) if span_attrs is not None else {}
            with get_tracer().span(span, **attrs):
                return run(*args, **kw)

        def run(*args, **kw):
            with self._lock:
                fn = self._exec.get(key)
            if fn is None:  # invalidated: rebuild from source
                with self._lock:
                    src = self._fn_src[key]
                    self.stats["serve_rebuilt"] += 1
                # jit caches traces by function identity, so re-jitting `src`
                # directly would replay the stale executable; a fresh wrapper
                # object forces a re-trace, baking in freshly-dispatched
                # kernel configs
                def fresh(*a, **k):
                    return src(*a, **k)

                fn = jax.jit(fresh) if self.jit else fresh
                with self._lock:
                    fn = self._exec.setdefault(key, fn)
            return fn(*args, **kw)

        return proxy


# -- module-level default service (the one-liner API) ---------------------------

_default: DispatchService | None = None
_default_lock = threading.Lock()


def get_service() -> DispatchService:
    global _default
    with _default_lock:
        if _default is None:
            _default = DispatchService()
        return _default


def configure(store: TuningStore | str | None = None, **kw) -> DispatchService:
    """(Re)build the process-wide default service, e.g.
    ``configure("results/store", tuner=BackgroundTuner(...))``."""
    global _default
    if isinstance(store, str):
        store = TuningStore(store)
    with _default_lock:
        _default = DispatchService(store, **kw)
        return _default


def dispatch(kernel: str, *args, **static_kw) -> Callable:
    return get_service().dispatch(kernel, *args, **static_kw)


def call(kernel: str, *args, **static_kw):
    return get_service().call(kernel, *args, **static_kw)
