"""Pass 1 — static config feasibility.

Declarative per-kernel constraint rules that judge a configuration against
the problem dimensions *without tracing or building anything*: structural
validity (required params present, tiles positive integers, variant choices
known, fuse factors dividing the step count), resource limits (estimated
VMEM footprint from the BlockSpec geometry via the analytic cost model),
and schedule-quality smells (MXU (8, 128) misalignment, lcm-padding blowup,
grid-size sanity).

Findings carry a stable machine-readable ``code`` (e.g.
``tile_not_positive:bi``, ``vmem_overflow``) and a ``severity``:

  * ``"error"`` — the config is invalid: it would fail to build/trace, or
    the cost model proves it cannot fit (VMEM over budget on a TPU-class
    target). Errors make :attr:`Feasibility.ok` false; the search path
    prunes these before acquisition scoring and ``DispatchService``
    quarantines matching store records without paying an ``eval_shape``.
  * ``"warn"`` — the config builds but is pathological (the paper's
    Floyd-Warshall failure mode): heavy padding waste, misaligned MXU
    tiles, oversized grids. Warnings never prune or quarantine; they feed
    the ``repro-analyze space`` audit.

The severity split is what keeps the pass zero-false-positive: a config is
only ever rejected for a reason that is *provably* fatal for that builder,
which the accepted-implies-builds property test pins for every registered
kernel.

Rules for new kernels go through :func:`register_rules`; kernels with no
registered rules (toy test kernels, third-party registrations) are treated
as feasible — the pass never guesses.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable, Iterable, Iterator, Mapping, Sequence

__all__ = [
    "Feasibility",
    "Finding",
    "KERNEL_RULES",
    "check_config",
    "feasibility_filter",
    "kernel_rules",
    "register_rules",
]

ERROR = "error"
WARN = "warn"

# grid-step budget before a schedule is flagged as pathological: a Pallas /
# XLA loop nest still compiles above this, it just spends its life in
# per-step overhead (warn-only, so the threshold only shapes the audit)
GRID_WARN_STEPS = 1 << 20
# padded-iteration blowup (vs the nominal iteration count) above which a
# host schedule is flagged — syr2k at N=240 with lcm(50, 128)=3200 padding
# sits near 178x, the audit's canonical pathology
PAD_WASTE_RATIO = 1.5


@dataclass(frozen=True)
class Finding:
    """One rule hit. ``code`` is stable across releases (tests and
    quarantine records key on it); ``message`` is for humans."""

    code: str
    severity: str
    message: str
    param: str | None = None

    def to_dict(self) -> dict:
        d = {"code": self.code, "severity": self.severity,
             "message": self.message}
        if self.param is not None:
            d["param"] = self.param
        return d


@dataclass(frozen=True)
class Feasibility:
    """Verdict for one (kernel, config, dims, target) combination."""

    findings: tuple[Finding, ...] = field(default_factory=tuple)

    @property
    def ok(self) -> bool:
        return not any(f.severity == ERROR for f in self.findings)

    @property
    def errors(self) -> tuple[Finding, ...]:
        return tuple(f for f in self.findings if f.severity == ERROR)

    @property
    def warnings(self) -> tuple[Finding, ...]:
        return tuple(f for f in self.findings if f.severity == WARN)

    @property
    def reasons(self) -> tuple[str, ...]:
        """Stable error codes — what lands in quarantine records."""
        return tuple(f.code for f in self.errors)

    def reason(self) -> str:
        """Single machine-readable reason string (codes joined by ``,``)."""
        return ",".join(self.reasons)


FEASIBLE = Feasibility()


@dataclass(frozen=True)
class RuleContext:
    """What a rule may consult besides the config itself."""

    kernel: str
    dims: tuple | None    # problem dims (kernels.problems order), if known
    target: str           # "host" | "tpu" | "cost"


class Rule:
    """Base class: a rule inspects (config, context) and yields findings.

    Rules must be total — any config dict, any dims (including ``None``)
    — and must never trace, build, or import jax at check time."""

    def check(self, cfg: Mapping, ctx: RuleContext) -> Iterator[Finding]:
        raise NotImplementedError

    def describe(self) -> str:
        return self.__class__.__name__


def _is_int(v) -> bool:
    return isinstance(v, int) and not isinstance(v, bool)


class RequiredParams(Rule):
    """Params the builder reads with ``cfg[name]`` — absence is a KeyError
    at build time, so it is an error here."""

    def __init__(self, *names: str):
        self.names = names

    def check(self, cfg, ctx):
        for name in self.names:
            if name not in cfg:
                yield Finding(f"missing_param:{name}", ERROR,
                              f"builder requires {name!r}", name)

    def describe(self):
        return f"required params: {', '.join(self.names)}"


class PositiveIntTiles(Rule):
    """Tile/block params must be positive integers: ``cdiv`` by zero and
    negative reshapes are build-time failures."""

    def __init__(self, *names: str):
        self.names = names

    def check(self, cfg, ctx):
        for name in self.names:
            if name not in cfg:
                continue  # RequiredParams owns absence
            v = cfg[name]
            if not _is_int(v):
                yield Finding(f"tile_not_int:{name}", ERROR,
                              f"{name}={v!r} is not an integer tile", name)
            elif v <= 0:
                yield Finding(f"tile_not_positive:{name}", ERROR,
                              f"{name}={v} must be positive", name)

    def describe(self):
        return f"positive integer tiles: {', '.join(self.names)}"


class ChoiceIn(Rule):
    """Variant-selector params the builder dispatches on (and raises for
    unknown values)."""

    def __init__(self, name: str, choices: Sequence):
        self.name = name
        self.choices = tuple(choices)

    def check(self, cfg, ctx):
        if self.name in cfg and cfg[self.name] not in self.choices:
            yield Finding(
                f"invalid_choice:{self.name}", ERROR,
                f"{self.name}={cfg[self.name]!r} not in {self.choices}",
                self.name)

    def describe(self):
        return f"{self.name} in {self.choices}"


class FuseDivides(Rule):
    """heat-3d: the builder asserts ``(2 * tsteps) % fuse_t == 0`` — a
    non-dividing fuse factor is a hard build failure."""

    def __init__(self, name: str = "fuse_t"):
        self.name = name

    def check(self, cfg, ctx):
        h = cfg.get(self.name, 1)
        if not _is_int(h) or h <= 0:
            yield Finding(f"fuse_not_positive:{self.name}", ERROR,
                          f"{self.name}={h!r} must be a positive integer",
                          self.name)
            return
        if ctx.dims is None or len(ctx.dims) < 2:
            return
        total = 2 * int(ctx.dims[1])
        if total % h != 0:
            yield Finding(
                f"fuse_indivisible:{self.name}", ERROR,
                f"{self.name}={h} does not divide 2*tsteps={total}",
                self.name)

    def describe(self):
        return f"{self.name} divides 2*tsteps"


class PageDividesSeq(Rule):
    """Paged-KV layout contract: the dispatch signature's seq dim is the
    paged cache's *bucket* — by construction a multiple of the config's
    ``page`` (``serve.kvcache`` rounds every view up to page granularity).
    A record whose ``page`` does not divide the signature's S describes a
    layout that cannot have produced this signature: serving it would pair
    a tuned page size with buckets it never sees, so the mismatch is fatal
    for the (cache layout, kernel) pair even though the builder itself —
    which only reads the view it is handed — would trace fine."""

    def __init__(self, name: str = "page", dim_index: int = 2):
        self.name = name
        self.dim_index = dim_index

    def check(self, cfg, ctx):
        v = cfg.get(self.name)
        if not (_is_int(v) and v > 0):
            return  # PositiveIntTiles owns malformed values
        if ctx.dims is None or self.dim_index >= len(ctx.dims):
            return
        s = int(ctx.dims[self.dim_index])
        if s % v != 0:
            yield Finding(
                f"page_indivisible:{self.name}", ERROR,
                f"{self.name}={v} does not divide the seq bucket S={s}",
                self.name)

    def describe(self):
        return f"{self.name} divides the signature's seq bucket"


class VmemBudget(Rule):
    """TPU-class targets only: the analytic cost model derives the VMEM
    footprint from the BlockSpec geometry; over-budget configs are the
    OOM-compile analog and are pruned as errors. Host schedules have no
    VMEM, so the rule is inert there."""

    def check(self, cfg, ctx):
        if ctx.target not in ("tpu", "cost") or ctx.dims is None:
            return
        from repro.kernels.cost import KERNEL_COST_FNS, VMEM_BYTES

        fn = KERNEL_COST_FNS.get(ctx.kernel)
        if fn is None:
            return
        try:
            _, info = fn(cfg, *ctx.dims)
        except Exception:
            # structurally invalid configs are other rules' findings; the
            # cost model choking on them must not mask those codes
            return
        if info.get("infeasible") == "vmem":
            yield Finding(
                "vmem_overflow", ERROR,
                f"estimated VMEM {info.get('vmem_bytes', 0):,} B exceeds "
                f"the {VMEM_BYTES:,} B per-core budget")

    def describe(self):
        return "estimated VMEM footprint within per-core budget (tpu/cost)"


class MxuAlign(Rule):
    """TPU-class targets: tiles off the (8, 128) sublane/lane grid pad in
    the MXU and waste systolic work. Warn-only — the kernels pad and run."""

    def __init__(self, *names: str):
        self.names = names

    def check(self, cfg, ctx):
        if ctx.target not in ("tpu", "cost"):
            return
        for name in self.names:
            v = cfg.get(name)
            if _is_int(v) and v > 0 and v % 8 != 0:
                yield Finding(
                    f"mxu_misaligned:{name}", WARN,
                    f"{name}={v} is not a multiple of the 8-sublane tile",
                    name)

    def describe(self):
        return f"MXU (8,128) alignment: {', '.join(self.names)} (tpu/cost)"


class LaneBlocks(Rule):
    """TPU-class targets: Mosaic refuses a block whose last dim is neither
    a multiple of the 128-lane tile nor the whole array dim (the rule the
    chip enforced on the first kernels brought up on it). ``axes`` maps
    each lane-dim tile param to the dim index it tiles; tiles are taken
    after the builder's clamp to the dim, and widened to the whole dim
    where they do not divide it, as ``grouped_tiles`` does. Only the
    Pallas impl has blocks."""

    def __init__(self, axes: Mapping[str, int]):
        self.axes = dict(axes)

    def check(self, cfg, ctx):
        if ctx.target not in ("tpu", "cost") or ctx.dims is None \
                or cfg.get("impl", "pallas") != "pallas":
            return
        for name, di in self.axes.items():
            v = cfg.get(name)
            if not (_is_int(v) and v > 0) or di >= len(ctx.dims):
                continue
            n = int(ctx.dims[di])
            t = min(v, n)
            t = t if n % t == 0 else n
            if t % 128 and t != n:
                yield Finding(
                    f"lane_misaligned:{name}", ERROR,
                    f"{name}={v} gives a {t}-wide block of a {n}-wide dim: "
                    f"neither a multiple of 128 nor the whole dim", name)

    def describe(self):
        return (f"lane-dim blocks {', '.join(self.axes)} are multiples of "
                f"128 or the whole dim (tpu/cost)")


class LcmPadding(Rule):
    """Host syr2k/covariance pad the square dim up to a multiple of
    ``lcm(bi, bj)`` (after the builder's ``min(tile, dim)`` clamp); mixed
    tile families (50 vs 128) blow this up — the audit's canonical
    pathology. Warn-only: the padded kernel is correct, just wasteful."""

    def __init__(self, pi: str, pj: str, dim_index: int):
        self.pi, self.pj, self.dim_index = pi, pj, dim_index

    def check(self, cfg, ctx):
        if ctx.target != "host" or ctx.dims is None:
            return
        bi, bj = cfg.get(self.pi), cfg.get(self.pj)
        if not (_is_int(bi) and bi > 0 and _is_int(bj) and bj > 0):
            return
        n = int(ctx.dims[self.dim_index])
        bi, bj = min(bi, n), min(bj, n)
        lcm = math.lcm(bi, bj)
        padded = -(-n // lcm) * lcm
        ratio = (padded / n) ** 2  # the padded dim is squared in the nest
        if ratio > PAD_WASTE_RATIO:
            yield Finding(
                "padding_waste", WARN,
                f"lcm({self.pi}={bi}, {self.pj}={bj})={lcm} pads "
                f"N={n} to {padded} (~{ratio:.1f}x the nominal work)")

    def describe(self):
        return (f"lcm({self.pi}, {self.pj}) padding blowup vs problem dim "
                f"(host)")


class GridBound(Rule):
    """Grid-size sanity: the number of block steps the schedule implies,
    after the builder's ``min(tile, dim)`` clamp. Oversized grids compile
    but drown in per-step overhead. ``axes`` maps tile params to the dim
    index they divide."""

    def __init__(self, axes: Mapping[str, int], steps: int = 1):
        self.axes = dict(axes)
        self.steps = steps  # outer sequential multiplier (e.g. FW rounds)

    def check(self, cfg, ctx):
        if ctx.dims is None:
            return
        total = self.steps
        for name, di in self.axes.items():
            v = cfg.get(name)
            if not (_is_int(v) and v > 0) or di >= len(ctx.dims):
                return
            n = int(ctx.dims[di])
            total *= -(-n // min(v, n))
        if total > GRID_WARN_STEPS:
            yield Finding(
                "grid_too_large", WARN,
                f"~{total:,} grid steps exceeds the {GRID_WARN_STEPS:,} "
                f"sanity bound")

    def describe(self):
        return f"grid steps over {', '.join(self.axes)} within sanity bound"


# ---------------------------------------------------------------------------
# per-kernel rule tables
# ---------------------------------------------------------------------------
# dims follow kernels.problems.BENCH_DIMS order for each kernel.

KERNEL_RULES: dict[str, tuple[Rule, ...]] = {
    "syr2k": (
        RequiredParams("bi", "bj", "bk"),
        PositiveIntTiles("bi", "bj", "bk"),
        VmemBudget(),
        MxuAlign("bi", "bj", "bk"),
        LcmPadding("bi", "bj", dim_index=0),
        GridBound({"bi": 0, "bj": 0, "bk": 1}),
    ),
    "mm3": (
        RequiredParams("bm", "bn", "bk"),
        PositiveIntTiles("bm", "bn", "bk"),
        VmemBudget(),
        MxuAlign("bm", "bn", "bk"),
        GridBound({"bm": 0, "bn": 4, "bk": 2}),
    ),
    "lu": (
        RequiredParams("bs"),
        PositiveIntTiles("bs", "bm", "bn"),
        VmemBudget(),
        MxuAlign("bs", "bm", "bn"),
        GridBound({"bs": 0}),
    ),
    "heat3d": (
        RequiredParams("bi"),
        PositiveIntTiles("bi"),
        FuseDivides("fuse_t"),
        VmemBudget(),
        GridBound({"bi": 0}, steps=2),
    ),
    "covariance": (
        RequiredParams("bi", "bj", "bk"),
        PositiveIntTiles("bi", "bj", "bk"),
        VmemBudget(),
        MxuAlign("bi", "bj", "bk"),
        LcmPadding("bi", "bj", dim_index=1),
        GridBound({"bi": 1, "bj": 1, "bk": 0}),
    ),
    "floyd_warshall": (
        RequiredParams("bs", "bi", "bj"),
        PositiveIntTiles("bs", "bi", "bj"),
        ChoiceIn("unroll", (1, 2, 4, 8)),
        VmemBudget(),
        MxuAlign("bi", "bj"),
        GridBound({"bs": 0, "bi": 0, "bj": 0}),
    ),
    "flash_attention": (
        ChoiceIn("impl", ("pallas", "xla")),
        PositiveIntTiles("bq", "bk"),
        VmemBudget(),
        MxuAlign("bq", "bk"),
        GridBound({"bq": 1, "bk": 2}),
    ),
    "decode_attention": (
        ChoiceIn("impl", ("pallas", "xla")),
        PositiveIntTiles("bk", "hg", "page"),
        PageDividesSeq("page", dim_index=2),
        VmemBudget(),
        MxuAlign("bk"),
        GridBound({"hg": 0, "bk": 2}),
    ),
    "grouped_matmul": (
        ChoiceIn("impl", ("pallas", "xla")),
        PositiveIntTiles("tm", "tk", "tn"),
        LaneBlocks({"tk": 1, "tn": 2}),
        VmemBudget(),
        MxuAlign("tm"),
        GridBound({"tm": 0, "tn": 2, "tk": 1}),
    ),
    "matmul": (
        PositiveIntTiles("bm", "bn", "bk"),
        VmemBudget(),
        MxuAlign("bm", "bn", "bk"),
        GridBound({"bm": 0, "bk": 1, "bn": 2}),
    ),
}


def kernel_rules(kernel: str) -> tuple[Rule, ...]:
    """The rule tuple for ``kernel`` (empty for unknown kernels)."""
    return KERNEL_RULES.get(kernel, ())


def register_rules(kernel: str, rules: Iterable[Rule],
                   *, replace: bool = False) -> None:
    """Attach feasibility rules to a kernel (tests, third-party kernels).
    Appends to any existing table unless ``replace``."""
    rules = tuple(rules)
    if replace or kernel not in KERNEL_RULES:
        KERNEL_RULES[kernel] = rules
    else:
        KERNEL_RULES[kernel] = KERNEL_RULES[kernel] + rules


def _dims_for(kernel: str, signature, dims) -> tuple | None:
    if dims is not None:
        return tuple(dims)
    if signature is None:
        return None
    from repro.kernels.problems import dims_from_signature

    try:
        return tuple(dims_from_signature(kernel, signature))
    except Exception:
        return None  # runtime signature shapes this table doesn't know


def check_config(
    kernel: str,
    config: Mapping,
    *,
    dims: tuple | None = None,
    signature=None,
    target: str = "host",
) -> Feasibility:
    """Statically judge ``config`` for ``kernel``.

    ``dims`` are the problem dims in :data:`~repro.kernels.problems.BENCH_DIMS`
    order; alternatively pass the store/runtime ``signature`` and the dims
    are recovered via ``dims_from_signature`` (unknown kernels or shapes
    degrade to dimension-independent rules only). Kernels with no
    registered rules are feasible by construction."""
    rules = KERNEL_RULES.get(kernel)
    if not rules:
        return FEASIBLE
    ctx = RuleContext(kernel=kernel, dims=_dims_for(kernel, signature, dims),
                      target=target)
    findings: list[Finding] = []
    for rule in rules:
        findings.extend(rule.check(config, ctx))
    if not findings:
        return FEASIBLE
    return Feasibility(tuple(findings))


def feasibility_filter(
    kernel: str,
    *,
    dims: tuple | None = None,
    signature=None,
    target: str = "host",
) -> Callable[[Mapping], bool] | None:
    """A ``config -> bool`` predicate for the search path (True = keep), or
    ``None`` when the kernel has no rules — callers skip the filtering pass
    entirely in that case. Only errors prune; warnings survive so the
    optimizer can still learn the pathological region is bad."""
    if not KERNEL_RULES.get(kernel):
        return None
    ctx_dims = _dims_for(kernel, signature, dims)

    def accept(cfg: Mapping) -> bool:
        return check_config(kernel, cfg, dims=ctx_dims, target=target).ok

    return accept
