"""Helpers for the readers of the program's own spans (``repro.obs``): the
spans recorded inside the measured or the traced window, the device's idle
gaps rebuilt from the trace, and how much of those gaps a set of spans
covers.

Spans and the driver's records share one clock: a span's
``time.perf_counter_ns()`` over 1e9 is the driver's ``time.perf_counter()``.
An op of the trace starts ``start`` seconds after ``bench.window`` opened,
and the driver reads ``trace_t0`` just after it opens, so the op sits at
host time ``trace_t0 + start``.

Every helper gives ``None`` where there is nothing sound to read: a program
that records no spans (no ``repro.obs.trace.recorded_spans``), or a ring
that dropped a span which ended after the window opened.
"""

from __future__ import annotations

import numpy as np

__all__ = ["JAX_EVENTS", "recorded", "within", "subtree", "ancestors",
           "merge", "covered", "idle_gaps", "compile_ms_per_prefill",
           "idle_compile_share"]

# the child spans repro.obs records for JAX's trace, lowering, backend
# compile and persistent-cache load
JAX_EVENTS = ("jax.trace", "jax.lower", "jax.compile", "jax.cache_load")


def recorded(since: float):
    """The program's spans, or ``None`` when it records none or its ring
    dropped a span that ended at or after ``since`` (host seconds)."""
    try:
        from repro.obs import trace
    except ImportError:
        return None
    read = getattr(trace, "recorded_spans", None)
    ring = getattr(trace, "get_span_ring", None)
    if read is None or ring is None:
        return None
    spans = read()
    # the ring keeps spans in the order they ended and drops the oldest
    if ring().dropped and (not spans or spans[0].end_ns * 1e-9 >= since):
        return None
    return spans


def within(spans, name: str, t0: float, t1: float) -> list:
    """Spans called ``name`` that lie wholly in ``[t0, t1]`` (seconds)."""
    lo, hi = int(t0 * 1e9), int(t1 * 1e9)
    return [s for s in spans if s.name == name and s.start_ns >= lo and s.end_ns <= hi]


def subtree(spans, root_id: int) -> list:
    """Every span below ``root_id`` (children, their children, ...)."""
    kids: dict[int, list] = {}
    for s in spans:
        kids.setdefault(s.parent, []).append(s)
    out, todo = [], [root_id]
    while todo:
        for s in kids.get(todo.pop(), ()):
            out.append(s)
            todo.append(s.id)
    return out


def ancestors(by_id: dict, span) -> list[str]:
    """Names of the spans above ``span``, innermost first."""
    names, pid = [], span.parent
    while pid and pid in by_id:
        names.append(by_id[pid].name)
        pid = by_id[pid].parent
    return names


def merge(starts, ends):
    """The union of intervals as sorted, disjoint ``(starts, ends)``."""
    s, e = np.asarray(starts, np.float64), np.asarray(ends, np.float64)
    if len(s) == 0:
        return s, e
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    first = np.ones(len(s), bool)
    first[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(first)
    last = np.append(idx[1:] - 1, len(s) - 1)
    return s[idx], reach[last]


def covered(a, b) -> float:
    """Length of the overlap of two unions of intervals, each given as
    ``merge`` returns it."""
    (a0, a1), (b0, b1) = a, b
    if len(a0) == 0 or len(b0) == 0:
        return 0.0
    cum = np.concatenate([[0.0], np.cumsum(a1 - a0)])

    def upto(x):   # length of ``a`` left of x
        k = np.searchsorted(a0, x, side="right")
        prev = np.maximum(k - 1, 0)
        part = np.where(k > 0, np.clip(x - a0[prev], 0.0, a1[prev] - a0[prev]), 0.0)
        return cum[prev] * (k > 0) + part

    return float(np.sum(upto(b1) - upto(b0)))


def idle_gaps(run):
    """The device's idle gaps in the traced window, on the host clock, as
    ``merge`` returns them; ``None`` without a trace. With several devices
    a gap is a time in which none of them ran an op."""
    rec, tr = run.records, run.trace
    if tr is None or rec.get("trace_t0") is None or tr.window_s <= 0:
        return None
    b0, b1 = merge(tr.starts, np.asarray(tr.starts) + np.asarray(tr.durations))
    edges = np.concatenate([[0.0], np.column_stack([b0, b1]).ravel(), [tr.window_s]])
    g0, g1 = edges[0::2], edges[1::2]
    keep = g1 > g0
    return g0[keep] + rec["trace_t0"], g1[keep] + rec["trace_t0"]


def compile_ms_per_prefill(run) -> float | None:
    """Mean over the ``serve.prefill`` spans that lie in the measured window
    of the time under their ``jax.*`` descendants (their union: a cache
    load inside a backend compile counts once), in ms."""
    rec = run.records
    spans = recorded(rec["t0"])
    if spans is None:
        return None
    roots = within(spans, "serve.prefill", rec["t0"], rec["t_end"])
    if not roots:
        return None
    total = 0.0
    for r in roots:
        jx = [s for s in subtree(spans, r.id) if s.name in JAX_EVENTS]
        s0, s1 = merge([s.start_ns * 1e-9 for s in jx], [s.end_ns * 1e-9 for s in jx])
        total += float(np.sum(s1 - s0))
    return 1e3 * total / len(roots)


def idle_compile_share(run) -> float | None:
    """Percent of the device's idle time in the traced window that lies
    under the union of the program's ``jax.*`` spans."""
    rec = run.records
    gaps = idle_gaps(run)
    if gaps is None:
        return None
    spans = recorded(rec["trace_t0"])
    if spans is None:
        return None
    idle = float(np.sum(gaps[1] - gaps[0]))
    if idle <= 0:
        return None
    jx = [s for s in spans if s.name in JAX_EVENTS]
    busy_host = merge([s.start_ns * 1e-9 for s in jx], [s.end_ns * 1e-9 for s in jx])
    return 100.0 * covered(gaps, busy_host) / idle
