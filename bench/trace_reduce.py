"""Reduction of a JAX profiler trace to the benchmark's device numbers.

A traced run writes an ``.xplane.pb`` under ``<dir>/plugins/profile/``.
This module reads it with ``jax.profiler.ProfileData`` and keeps, within
the traced window (the host annotation ``bench.window``):

* the device's busy time: the union of the intervals of the ops on the
  ``XLA Ops`` line of every TPU plane, averaged over the TPU planes;
* each op's label (``<module>:<op>``; a Pallas kernel is named by
  ``bench/kernels.json``), start and duration, for kernel device time;
* the idle gaps between busy intervals, each put down to the innermost
  ``bench.*`` host annotation that covers its midpoint.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re

import numpy as np

__all__ = ["TraceSummary", "reduce_dir", "reduce_profile", "op_label",
           "load_kernel_table"]

_KERNELS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "kernels.json")
_CONTAINERS = ("while", "conditional", "call")
_OPERAND = re.compile(r"\b(pred|s8|s16|s32|s64|u8|u16|u32|u64|bf16|f16|f32|f64)"
                      r"\[([\d,]*)\]")


def load_kernel_table(path: str = _KERNELS) -> dict:
    with open(path) as f:
        return json.load(f)["kernels"]


def _custom_call(name: str):
    """(target, [dtype:rank, ...]) of a custom-call op, else None."""
    m = re.search(r'custom_call_target="([^"]+)"', name)
    if m is None:
        return None
    body = re.search(r"custom-call\((.*?)\), custom_call_target", name)
    ops = []
    if body is not None:
        for dt, dims in _OPERAND.findall(body.group(1)):
            ops.append(f"{dt}:{len(dims.split(',')) if dims else 0}")
    return m.group(1), ops


def op_label(name: str, kernels: dict) -> str:
    """A stable label for an XLA op event: the kernel's name for a Pallas
    call that ``kernels`` recognises, else the HLO opcode (with a fusion's
    kind), without the instruction's running number."""
    cc = _custom_call(name)
    if cc is not None:
        target, ops = cc
        for kname, k in kernels.items():
            if k["target"] == target and k["operands"] == ops:
                return kname
        return target
    m = re.search(r"=\s*\S+\s+([a-z][\w-]*)\(", name)
    if m is None:
        m = re.search(r"=\s*\([^=]*?\)\s+([a-z][\w-]*)\(", name)
    op = m.group(1) if m else name.split(" ")[0].lstrip("%").split(".")[0]
    if op == "fusion":
        k = re.search(r"kind=(k\w+)", name)
        op = f"fusion({k.group(1)})" if k else op
    return op


def _union(starts, ends):
    """Total length of the union of intervals, and the merged intervals."""
    if len(starts) == 0:
        return 0.0, []
    order = np.argsort(starts, kind="stable")
    merged = []
    cs, ce = starts[order[0]], ends[order[0]]
    for i in order[1:]:
        s, e = starts[i], ends[i]
        if s > ce:
            merged.append((cs, ce))
            cs, ce = s, e
        else:
            ce = max(ce, e)
    merged.append((cs, ce))
    return float(sum(e - s for s, e in merged)), merged


def _module_name(name: str) -> str:
    name = re.sub(r"\(\d+\)$", "", name)
    return name[4:] if name.startswith("jit_") else name


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    labels: np.ndarray        # (n,) op label, "<module>:<op>"
    starts: np.ndarray        # (n,) seconds from the window's start
    durations: np.ndarray     # (n,) seconds
    idle_by_host: dict        # host annotation -> idle seconds
    n_devices: int

    def _select(self, kernel: str, module: str | None) -> np.ndarray:
        if module is not None:
            return self.labels == f"{module}:{kernel}"
        return np.array([lab.endswith(":" + kernel) for lab in self.labels], bool)

    def seconds_of(self, kernel: str, module: str | None = None) -> float:
        """Device seconds of the ops labelled ``kernel`` (inside ``module``
        when given), averaged over the devices."""
        sel = self._select(kernel, module)
        return float(self.durations[sel].sum()) / max(self.n_devices, 1)

    def count_of(self, kernel: str, module: str | None = None) -> int:
        return int(self._select(kernel, module).sum()) // max(self.n_devices, 1)

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took most device time (control-flow ops, whose
        spans hold their bodies' ops, left out), and idle time by what the
        host was doing."""
        tot: dict[str, float] = {}
        for lab, d in zip(self.labels.tolist(), self.durations.tolist()):
            if lab.rsplit(":", 1)[-1] in _CONTAINERS:
                continue
            tot[lab] = tot.get(lab, 0.0) + d / max(self.n_devices, 1)
        ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, float(v)] for k, v in ops],
                "idle_gaps": [[k, float(v)] for k, v in gaps]}


def reduce_profile(pd, *, annotation_prefix: str = "bench.",
                   window: str = "bench.window",
                   kernels: dict | None = None) -> TraceSummary:
    kernels = load_kernel_table() if kernels is None else kernels
    host = []   # (start, end, name) of bench annotations, ns
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(annotation_prefix):
                    host.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    wins = [h for h in host if h[2] == window]
    if not wins:
        raise ValueError(f"trace holds no {window!r} annotation")
    w0, w1 = wins[0][0], wins[0][1]
    inner = [h for h in host if h[2] != window]
    hs = np.array([h[0] for h in inner], dtype=np.float64)
    he = np.array([h[1] for h in inner], dtype=np.float64)
    hn = [h[2] for h in inner]

    labels, starts, durs = [], [], []
    busy, idle_by_host, n_dev = 0.0, {}, 0
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        mods, ops = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                mods = [(e.start_ns, e.start_ns + e.duration_ns, _module_name(e.name))
                        for e in line.events]
            elif line.name == "XLA Ops":
                ops = [(e.start_ns, e.duration_ns, e.name) for e in line.events]
        if not ops and not mods:
            continue
        n_dev += 1
        mods.sort()
        mstart = np.array([m[0] for m in mods], dtype=np.float64)
        s = np.array([o[0] for o in ops], dtype=np.float64)
        d = np.array([o[1] for o in ops], dtype=np.float64)
        keep = (s + d > w0) & (s < w1)
        cs = np.clip(s[keep], w0, w1)
        ce = np.clip(s[keep] + d[keep], w0, w1)
        b, merged = _union(cs, ce)
        busy += b
        label_cache: dict[str, str] = {}
        for (st, du, name), c0, c1 in zip([o for o, k in zip(ops, keep) if k], cs, ce):
            lab = label_cache.get(name)
            if lab is None:
                lab = label_cache[name] = op_label(name, kernels)
            i = int(np.searchsorted(mstart, st, side="right")) - 1
            mod = mods[i][2] if i >= 0 and st < mods[i][1] else "?"
            labels.append(f"{mod}:{lab}")
            starts.append((c0 - w0) * 1e-9)
            durs.append((c1 - c0) * 1e-9)
        # idle gaps between merged busy intervals, inside the window
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = (g0 + g1) / 2
            cover = np.flatnonzero((hs <= mid) & (he >= mid))
            who = hn[cover[np.argmin(he[cover] - hs[cover])]] if len(cover) else "(none)"
            who = who[len(annotation_prefix):] if who.startswith(annotation_prefix) else who
            idle_by_host[who] = idle_by_host.get(who, 0.0) + (g1 - g0) * 1e-9
    if n_dev == 0:
        raise ValueError("trace holds no TPU device plane")
    idle_by_host = {k: v / n_dev for k, v in idle_by_host.items()}
    return TraceSummary(window_s=(w1 - w0) * 1e-9, busy_s=busy * 1e-9 / n_dev,
                        labels=np.array(labels, dtype=object),
                        starts=np.array(starts), durations=np.array(durs),
                        idle_by_host=idle_by_host, n_devices=n_dev)


def reduce_dir(trace_dir: str, **kw) -> TraceSummary:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_profile(ProfileData.from_file(files[-1]), **kw)
