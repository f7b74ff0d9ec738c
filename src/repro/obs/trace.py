"""Span tracing: an in-memory ring of spans, mirrored into the JAX
profiler's trace while one is collected, and optionally appended to a
Chrome-trace-event JSONL file.

Every span (``with span("serve.prefill", batch=1): ...``) records its name,
a span id, its parent's id (the innermost span open on the same thread), its
start and end as ``time.perf_counter_ns()``, its thread and its scalar
attributes into a bounded process-wide :class:`SpanRing` (65,536 spans, on
by default); what the ring drops is counted in ``obs_spans_dropped_total``.
:func:`recorded_spans` reads the ring and :func:`dump_recorded` writes it out
as Chrome-trace JSONL.

While a JAX profiler session is collecting, each span also enters a
``jax.profiler.TraceAnnotation`` of its name, so it sits on the host plane of
the ``.xplane.pb`` beside the device's ops. Outside a session no annotation
is entered. :func:`install_jax_hooks` (called by ``repro.serve`` and
``repro.dispatch``, so this module imports without jax) wires the profiler
check and a ``jax.monitoring`` listener that records each trace, lowering,
backend compile and persistent-cache load as a child span (``jax.trace``,
``jax.lower``, ``jax.compile``, ``jax.cache_load``) of the innermost open
span, and counts them in ``jax_compile_events_total{event, span}`` and
``jax_compile_seconds{event, span}``.

The file tracer is off by default: :func:`get_tracer` returns
:data:`NULL_TRACER`, whose spans go to the ring alone, unless
:func:`configure_tracer` was called or the ``REPRO_TRACE=path`` environment
variable names a trace file. Each line of that file is one Chrome trace
event (complete ``"ph": "X"`` spans with microsecond ``ts``/``dur``, ``"i"``
instants, ``"M"`` metadata), so it is valid JSONL — crash-safe, torn-tail
tolerant via :mod:`repro.core.jsonl` — and converts to a
Perfetto/``chrome://tracing``-loadable ``{"traceEvents": [...]}`` JSON via
:func:`export_chrome_trace` (or ``repro-obs summarize --perfetto out.json``).
"""

from __future__ import annotations

import collections
import itertools
import json
import os
import threading
import time
from typing import Iterator, NamedTuple

from repro.core.jsonl import repair_torn_tail
from repro.obs.metrics import get_registry

__all__ = [
    "Tracer",
    "NULL_TRACER",
    "SpanRecord",
    "SpanRing",
    "get_tracer",
    "configure_tracer",
    "span",
    "instant",
    "recorded_spans",
    "dump_recorded",
    "get_span_ring",
    "set_span_ring",
    "install_jax_hooks",
    "iter_trace",
    "validate_trace",
    "export_chrome_trace",
]

TRACE_ENV = "REPRO_TRACE"
RING_SPANS = 65536

# the Chrome-trace timestamps of this process: wall-clock microseconds,
# advanced by perf_counter so that traces of one host align across processes
_WALL_NS0 = time.time_ns()
_PERF_NS0 = time.perf_counter_ns()


def _chrome_us(t_ns: int) -> int:
    return (_WALL_NS0 + t_ns - _PERF_NS0) // 1000


class SpanRecord(NamedTuple):
    """One finished span. ``parent`` is 0 for a span opened with none open
    on its thread; times are ``time.perf_counter_ns()``."""

    id: int
    parent: int
    name: str
    start_ns: int
    end_ns: int
    tid: int
    attrs: dict


class SpanRing:
    """The last ``maxlen`` finished spans, oldest (by end) first. Appends
    take no lock (``deque.append`` is atomic); a span pushed out of a full
    ring is counted in ``dropped`` and in ``obs_spans_dropped_total``. It
    holds finished ``_Span`` objects and ``SpanRecord`` tuples, and reads
    both as ``SpanRecord``."""

    def __init__(self, maxlen: int = RING_SPANS):
        self.maxlen = int(maxlen)
        self.dropped = 0
        self.buf: collections.deque = collections.deque(maxlen=self.maxlen)

    def append(self, rec) -> None:
        if len(self.buf) == self.maxlen:
            self.drop()
        self.buf.append(rec)

    def drop(self) -> None:
        self.dropped += 1
        get_registry().add("obs_spans_dropped_total")

    def spans(self) -> list[SpanRecord]:
        return [r.record() if isinstance(r, _Span) else SpanRecord(*r)
                for r in list(self.buf)]


class _Stack(list):
    """One thread's open spans, innermost last, tagged with the thread."""

    __slots__ = ("tid",)


_ring = SpanRing()
_ids = itertools.count(1)
_local = threading.local()
# set by install_jax_hooks(): the profiler's "is a session collecting"
# check and its host-span annotation
_profiling = None
_annotation = None


def get_span_ring() -> SpanRing:
    return _ring


def set_span_ring(ring: SpanRing) -> SpanRing:
    """Swap the process ring (tests use this for isolation); returns the
    one it replaces."""
    global _ring
    old, _ring = _ring, ring
    return old


def recorded_spans() -> list[SpanRecord]:
    """The spans in the process ring, oldest (by end) first."""
    return _ring.spans()


def _open_spans() -> _Stack:
    """This thread's open spans (``_local.stack``)."""
    try:
        return _local.stack
    except AttributeError:
        stack = _local.stack = _Stack()
        stack.tid = threading.get_ident()
        return stack


def _chrome_event(name, t0, t1, tid, attrs, pid) -> dict:
    ts = _chrome_us(t0)
    ev = {"name": name, "cat": "repro", "ph": "X", "ts": ts,
          "dur": max(0, _chrome_us(t1) - ts), "pid": pid, "tid": tid}
    if attrs:
        ev["args"] = attrs
    return ev


class _Span:
    # the hot path of every instrumented layer: plain attribute work, one
    # clock read at each end, and the finished span itself goes to the ring
    __slots__ = ("name", "attrs", "file", "id", "parent", "t0", "t1", "ann",
                 "stack")

    def __init__(self, name: str, attrs: dict, file: "Tracer | None"):
        self.name = name
        self.attrs = attrs
        self.file = file

    def __enter__(self) -> "_Span":
        try:
            stack = _local.stack
        except AttributeError:
            stack = _open_spans()
        self.stack = stack
        self.parent = stack[-1].id if stack else 0
        self.id = next(_ids)
        stack.append(self)
        if _profiling is not None and _profiling():
            self.ann = _annotation(self.name)
            self.ann.__enter__()
        else:
            self.ann = None
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.t1 = time.perf_counter_ns()
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
            self.ann = None
        stack = self.stack
        if stack[-1] is self:
            stack.pop()
        else:
            stack.remove(self)
        if exc_type is not None:
            self.attrs = dict(self.attrs, error=exc_type.__name__)
        ring = _ring
        if len(ring.buf) == ring.maxlen:
            ring.drop()
        ring.buf.append(self)
        if self.file is not None:
            self.file.emit(_chrome_event(self.name, self.t0, self.t1,
                                         stack.tid, self.attrs, self.file._pid))

    def record(self) -> SpanRecord:
        return SpanRecord(self.id, self.parent, self.name, self.t0, self.t1,
                          self.stack.tid, self.attrs)


class NullTracer:
    """No trace file: spans go to the ring (and the profiler) alone."""

    enabled = False
    path = None

    def span(self, name: str, **attrs) -> _Span:
        return _Span(name, attrs, None)

    def instant(self, name: str, **attrs) -> None:
        pass

    def emit(self, event: dict) -> None:
        pass

    def close(self) -> None:
        pass


NULL_TRACER = NullTracer()


class Tracer:
    """Appends one trace event per line to ``path`` (spans also go to the
    ring). Thread-safe: one lock around the file write."""

    enabled = True

    def __init__(self, path: str, process_name: str | None = None):
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        repair_torn_tail(path)
        self.path = path
        self._f = open(path, "a")
        self._lock = threading.Lock()
        self._pid = os.getpid()
        if process_name:
            self.emit({"name": "process_name", "ph": "M",
                       "ts": _chrome_us(time.perf_counter_ns()),
                       "pid": self._pid, "tid": 0,
                       "args": {"name": process_name}})

    def span(self, name: str, **attrs) -> _Span:
        return _Span(name, attrs, self)

    def instant(self, name: str, **attrs) -> None:
        ev = {"name": name, "cat": "repro", "ph": "i", "s": "t",
              "ts": _chrome_us(time.perf_counter_ns()), "pid": self._pid,
              "tid": threading.get_ident()}
        if attrs:
            ev["args"] = attrs
        self.emit(ev)

    def emit(self, event: dict) -> None:
        line = json.dumps(event, default=str) + "\n"
        with self._lock:
            f = self._f
            if f is None or f.closed:
                return  # closed tracer: drop, never raise on a serving path
            f.write(line)
            f.flush()

    def close(self) -> None:
        with self._lock:
            if self._f is not None and not self._f.closed:
                self._f.close()


# -- process-wide default tracer -------------------------------------------------

_tracer: Tracer | NullTracer | None = None
_tracer_lock = threading.Lock()


def get_tracer() -> "Tracer | NullTracer":
    """The process tracer: configured one, else ``REPRO_TRACE`` env
    activation, else the ring-only :data:`NULL_TRACER`."""
    global _tracer
    t = _tracer
    if t is not None:
        return t
    with _tracer_lock:
        if _tracer is None:
            path = os.environ.get(TRACE_ENV)
            _tracer = Tracer(path) if path else NULL_TRACER
        return _tracer


def configure_tracer(path: "str | Tracer | None",
                     process_name: str | None = None) -> "Tracer | NullTracer":
    """Set the process tracer (a path, a ready Tracer, or None to disable).
    Returns the active tracer."""
    global _tracer
    with _tracer_lock:
        if _tracer is not None and _tracer.enabled:
            _tracer.close()
        if path is None:
            _tracer = NULL_TRACER
        elif isinstance(path, (Tracer, NullTracer)):
            _tracer = path
        else:
            _tracer = Tracer(path, process_name=process_name)
        return _tracer


def span(name: str, **attrs):
    """``with obs.span("campaign.ask", learner="RF"): ...`` into the ring,
    and into the trace file when one is configured."""
    t = _tracer
    if t is None:
        t = get_tracer()
    return _Span(name, attrs, t if t.enabled else None)


def instant(name: str, **attrs) -> None:
    get_tracer().instant(name, **attrs)


def dump_recorded(path: str) -> int:
    """Write the ring as Chrome-trace JSONL (what the file tracer writes,
    span and parent ids under ``args``). Returns the number of spans."""
    spans = recorded_spans()
    pid = os.getpid()
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(path, "w") as f:
        for s in spans:
            ev = _chrome_event(s.name, s.start_ns, s.end_ns, s.tid,
                               dict(s.attrs, span_id=s.id, parent_id=s.parent),
                               pid)
            f.write(json.dumps(ev, default=str) + "\n")
    return len(spans)


# -- JAX: profiler mirroring and compile events ---------------------------------

JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax.cache_load",
}
_jax_lock = threading.Lock()


def _on_jax_duration(event: str, secs: float, **kw) -> None:
    """A JAX compile-path event, reported as it ends on the thread that
    paid for it: a child span of the innermost open span, and the
    ``jax_compile_*`` counters labelled with that span's name."""
    name = JAX_EVENTS.get(event)
    if name is None:
        return
    t1 = time.perf_counter_ns()
    t0 = t1 - int(secs * 1e9)
    stack = _open_spans()
    owner = stack[-1] if stack else None
    attrs = {"seconds": secs}
    _ring.append(SpanRecord(next(_ids), owner.id if owner is not None else 0,
                            name, t0, t1, stack.tid, attrs))
    tracer = get_tracer()
    if tracer.enabled:
        tracer.emit(_chrome_event(name, t0, t1, stack.tid, attrs, tracer._pid))
    where = owner.name if owner is not None else "none"
    reg = get_registry()
    reg.add("jax_compile_events_total", event=name, span=where)
    reg.add("jax_compile_seconds", secs, event=name, span=where)


def install_jax_hooks() -> None:
    """Once per process: mirror spans into the JAX profiler's trace while a
    session collects, and record JAX's compile-path events as spans."""
    global _profiling, _annotation
    if _profiling is not None:
        return
    with _jax_lock:
        if _profiling is not None:
            return
        import jax

        jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
        _annotation = jax.profiler.TraceAnnotation
        _profiling = jax.profiler.TraceAnnotation.is_enabled


# -- validation / export ---------------------------------------------------------

_REQUIRED = ("name", "ph", "ts", "pid", "tid")


def iter_trace(path: str) -> Iterator[dict]:
    """Parsed events, one per valid line; blank/torn/garbage lines skipped."""
    if not os.path.exists(path):
        return
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                ev = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(ev, dict):
                yield ev


def validate_trace(path: str) -> dict:
    """Structural check of a trace file: every parseable line must be a
    Chrome trace event (required keys present, ``X`` spans carry ``dur``).
    Returns ``{"ok", "events", "invalid", "skipped", "names"}`` — ``ok`` is
    False when the file is missing/empty or any *parsed* event is malformed.
    Unparseable lines (a torn tail from a killed writer) are counted in
    ``skipped`` and do not fail validation: the JSONL contract is that a
    torn fragment stays an isolated bad line, never corrupts its neighbors."""
    events = 0
    invalid = 0
    skipped = 0
    names: set[str] = set()
    if not os.path.exists(path):
        return {"ok": False, "events": 0, "invalid": 0, "skipped": 0, "names": []}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                ev = json.loads(line)
            except json.JSONDecodeError:
                skipped += 1
                continue
            if not isinstance(ev, dict) or not all(k in ev for k in _REQUIRED) \
                    or (ev["ph"] == "X" and "dur" not in ev):
                invalid += 1
                continue
            events += 1
            names.add(str(ev["name"]))
    return {
        "ok": events > 0 and invalid == 0,
        "events": events,
        "invalid": invalid,
        "skipped": skipped,
        "names": sorted(names),
    }


def export_chrome_trace(src: str, out: str) -> int:
    """Wrap trace JSONL into a ``{"traceEvents": [...]}`` JSON file that
    Perfetto / ``chrome://tracing`` loads directly. Returns event count."""
    events = [ev for ev in iter_trace(src)
              if all(k in ev for k in _REQUIRED)]
    parent = os.path.dirname(out)
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(out, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return len(events)
