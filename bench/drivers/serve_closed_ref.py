"""Closed-loop serving driver for any configuration: the loop of
``serve_closed.py``, with the plain reference that the configuration names
(``bench/reference/<config["reference"]>.py``) and its weight adapter
``to_program``.

A private copy of ``serve_closed.py`` is loaded under another module name
and given the reference, its ``to_program`` and a check of the widest gap
by which a served token's logit lies below the reference's best, over a
seeded sample of finished requests (the longest among them, as
``serve_closed.py`` picks).

Where the limits file gives ``max_gap`` a ``margin``, the widest gap is
taken over the positions whose router margin in the reference (the
``top_k``-th largest router logit less the next, the least over the
layers; ``score_routed``) is at least that. Where two experts' router
logits lie within rounding of each other, the served bf16 model and the
float32 reference can route the token differently, and its logits then
differ by as much as a lower precision would make them: such a position
tells nothing of the system. The margin is the reference's own, so what
the check keeps does not depend on the system under test. The readings
also give the widest gap over the positions kept at each margin of
``MARGINS``, from which the cell's margin and limit are set.
"""

from __future__ import annotations

import functools
import importlib
import os

import numpy as np

import jax.numpy as jnp

from bench.harness import load_module

MARGINS = (0.0, 0.02, 0.05, 0.1, 0.2)


def check(ref, ctx, w, dims, finished, max_len, limits):
    """The widest gap of a seeded sample of finished requests against the
    reference, at the positions that the limits' ``margin`` keeps. With
    ``ctx.control`` the control is judged in the system's place: at the
    same positions, the token that the float8 reference puts first.
    Returns the checks and both readings."""
    lim = limits["max_gap"]
    if not finished:
        return {"max_gap": (float("inf"), float(lim["limit"]))}, None
    k = int(ctx.traffic["check"]["requests"])
    rng = np.random.default_rng(ctx.seed_for("check"))
    order = sorted(finished, key=lambda r: -len(r.tokens))
    rest = order[1:]
    pick = [order[0]] + [rest[i] for i in sorted(rng.choice(len(rest), min(k - 1, len(rest)),
                                                            replace=False))]
    block = 256
    S = -(-max_len // block) * block
    gaps, gaps_c, margins = [], [], []
    for r in pick:
        P, out = r.req.prompt_len, np.asarray(r.tokens, np.int32)
        seq = np.zeros(S, np.int32)
        seq[:P] = r.req.prompt
        seq[P:P + len(out)] = out
        nxt = jnp.asarray(np.concatenate([seq[1:], np.zeros(1, np.int32)]))
        at = slice(P - 1, P - 1 + len(out))
        if "margin" in lim:
            best, picked, _, margin = ref.score_routed(w, jnp.asarray(seq), nxt, dims=dims)
            margins.append(np.asarray(margin)[at])
        else:
            best, picked, _ = ref.score(w, jnp.asarray(seq), nxt, dims=dims)
        gaps.append(np.asarray(best - picked)[at])
        if ctx.control:
            _, _, top_c = ref.score(w, jnp.asarray(seq), nxt, dims=dims, quant="fp8")
            _, picked_c, _ = ref.score(w, jnp.asarray(seq), top_c, dims=dims)
            gaps_c.append(np.asarray(best - picked_c)[at])

    gaps = np.concatenate(gaps)
    margin = np.concatenate(margins) if margins else None
    keep = margin >= float(lim["margin"]) if margins else np.ones(len(gaps), bool)

    def reading(g):
        out = {"max_gap": float(np.max(g[keep], initial=0.0))}
        if margin is not None:
            out["by_margin"] = {str(m): float(np.max(g[margin >= m], initial=0.0))
                                for m in MARGINS}
        return out

    program = reading(gaps)
    control = reading(np.concatenate(gaps_c)) if ctx.control else None
    judged = control if ctx.control else program
    readings = {"program": program, "control": control, "tokens": int(len(gaps)),
                "kept": int(keep.sum()), "requests": len(pick)}
    if margin is not None:
        readings["kept_by_margin"] = {str(m): int(np.sum(margin >= m)) for m in MARGINS}
    return {"max_gap": (judged["max_gap"], float(lim["limit"]))}, readings


def run(ctx):
    """``serve_closed.py``'s loop on a private copy of it, set to the
    configuration's reference and its weight adapter (``serve_closed.py``'s
    own for ``dense_gqa``, which has none)."""
    base = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "serve_closed.py"), "bench_driver_serve_closed_ref_base")
    ref = importlib.import_module(f"bench.reference.{ctx.config['reference']}")
    base.ref = ref
    base.to_program = getattr(ref, "to_program", base.to_program)
    base._check = functools.partial(check, ref)
    return base.run(ctx)
