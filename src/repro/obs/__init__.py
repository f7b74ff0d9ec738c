"""repro.obs — unified observability: metrics, tracing, exposition.

Three layers, each usable alone:

* :mod:`repro.obs.metrics` — a process-wide :class:`MetricsRegistry` of
  counters, gauges, and log-bucketed latency histograms with *fixed* bucket
  boundaries, so histograms merge deterministically across threads, processes,
  and hosts. Recording is lock-free (per-thread shards); folding happens only
  at snapshot time.
* :mod:`repro.obs.trace` — spans with parent ids on the
  ``perf_counter_ns`` clock, kept in a bounded in-memory ring (on by
  default; :func:`recorded_spans`), mirrored into the JAX profiler's trace
  while a session collects, with JAX's trace/lower/compile/cache-load
  events recorded as child spans of the span that caused them. A
  Chrome-trace-event JSONL file is written as well when
  ``configure_tracer(path)`` or ``REPRO_TRACE=path`` asks for it;
  ``repro-obs summarize --perfetto out.json`` wraps the JSONL into a
  Perfetto-loadable ``{"traceEvents": [...]}`` file.
* :mod:`repro.obs.export` — JSONL snapshot writer, Prometheus text
  exposition, and the stdlib-``http.server`` :class:`ObsServer` serving
  ``/metrics`` + ``/snapshot``.

The serving/tuning stack (``repro.dispatch``, ``repro.engine``,
``repro.fleet``) records into the default registry and traces through the
default tracer; see README "Observability" for the metric names and label
schema.
"""

from repro.obs.metrics import (
    BUCKET_BOUNDS,
    Histogram,
    MetricsRegistry,
    get_registry,
    histogram_quantile,
    merge_snapshots,
    set_registry,
    summarize_histograms,
)
from repro.obs.trace import (
    NULL_TRACER,
    SpanRecord,
    Tracer,
    configure_tracer,
    dump_recorded,
    export_chrome_trace,
    get_tracer,
    install_jax_hooks,
    recorded_spans,
    span,
    validate_trace,
)
from repro.obs.export import (
    ObsServer,
    prometheus_text,
    read_snapshot_file,
    write_snapshot,
)

__all__ = [
    "BUCKET_BOUNDS",
    "Histogram",
    "MetricsRegistry",
    "get_registry",
    "set_registry",
    "histogram_quantile",
    "merge_snapshots",
    "summarize_histograms",
    "Tracer",
    "NULL_TRACER",
    "configure_tracer",
    "get_tracer",
    "span",
    "SpanRecord",
    "recorded_spans",
    "dump_recorded",
    "install_jax_hooks",
    "validate_trace",
    "export_chrome_trace",
    "ObsServer",
    "prometheus_text",
    "write_snapshot",
    "read_snapshot_file",
]
