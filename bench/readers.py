"""Helpers the per-layer metric readers share: which records fall in the
measured or the traced window, and the roofline arithmetic."""

from __future__ import annotations

import numpy as np

from bench import flops

__all__ = ["in_window", "in_trace", "idle_share", "roofline_share",
           "compiles_in_window"]


def in_window(rec: dict, starts, ends) -> np.ndarray:
    """Mask of spans that lie wholly in the measured window."""
    return (np.asarray(starts) >= rec["t0"]) & (np.asarray(ends) <= rec["t_end"])


def in_trace(rec: dict, starts, ends) -> np.ndarray:
    """Mask of spans that lie wholly in the traced part of the window."""
    if rec.get("trace_t0") is None or rec.get("trace_t1") is None:
        return np.zeros(len(starts), bool)
    return (np.asarray(starts) >= rec["trace_t0"]) & (np.asarray(ends) <= rec["trace_t1"])


def idle_share(run):
    """Percent of the traced window in which no op ran on the device."""
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)


def roofline_share(run, kernel: str, module: str, work) -> float | None:
    """Percent: least time of ``work`` (a list of (flops, bytes)) over the
    device seconds of ``kernel`` ops inside ``module`` in the trace."""
    if run.trace is None or not work:
        return None
    t = run.trace.seconds_of(kernel, module=module)
    if t <= 0:
        return None
    least = sum(flops.least_seconds(f, b, run.peaks)[0] for f, b in work)
    return 100.0 * least / t


def compiles_in_window(run) -> int:
    """Backend compiles (or persistent-cache loads) inside the window."""
    c = np.asarray(run.records["compiles"])
    return int(((c >= run.records["t0"]) & (c <= run.records["t_end"])).sum())
