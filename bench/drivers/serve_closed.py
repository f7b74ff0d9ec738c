"""Closed-loop serving driver: a fixed pool of clients, each sending its
next request as soon as its previous one is served, over the system's
public serving API.

The loop is the system's continuous-batching pattern: at each round
boundary every free slot admits a waiting request (``repro.serve.prefill``
then ``PagedKVCache.admit``), then the live slots decode up to
``round_cap`` steps together through the dispatched serve step on a
page-bucketed view of the cache (``view`` / ``writeback``). The batch is
rounded up the {1, 2, 4, ...} ladder with free slots. Each step's tokens
are copied to the host as they are made, as a streaming server sends
them; that copy's completion is the token's time.

Set-up makes the weights, warms every shape the cell's traffic uses (each
prompt length, and the serve step at each page bucket) and fills the slots.
The window then runs for ``--seconds``. After it closes, a seeded sample of
the requests it finished, the longest among them, is scored by the plain
reference (``bench/reference/dense_gqa.py``): the check is the widest gap
by which a served token's logit lies below the reference's best. With the
control switched on, the float8 reference's first choices at the same
positions are judged in place of the served tokens.
"""

from __future__ import annotations

import gc
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from bench import loadgen
from bench.harness import DriverResult, load_json, memory_peak_bytes
from bench.reference import dense_gqa as ref

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def program_config(config: dict):
    from repro.models.common import ArchConfig

    return ArchConfig(**config["program"], dtype=jnp.dtype(config["dtype"]))


def to_program(w: dict) -> dict:
    """The reference's weights in the system's parameter layout. The
    system stores each norm gain as its offset from 1."""
    lw = w["layers"]
    layers = {"ln1": lw["attn_norm"] - 1.0, "ln2": lw["mlp_norm"] - 1.0,
              "wq": lw["q"], "wk": lw["k"], "wv": lw["v"], "wo": lw["o"],
              "mlp": {"wg": lw["gate"], "wu": lw["up"], "wd": lw["down"]}}
    if "q_bias" in lw:
        layers.update(bq=lw["q_bias"], bk=lw["k_bias"], bv=lw["v_bias"])
    return {"embed": w["embed"], "final_norm": w["norm"] - 1.0, "layers": layers}


def ladder(n: int, cap: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


class _Req:
    __slots__ = ("req", "t_sent", "tokens", "times", "t_done")

    def __init__(self, req, t_sent):
        self.req, self.t_sent = req, t_sent
        self.tokens, self.times, self.t_done = [], [], None


class _Compiles:
    """Host times of every compile (or persistent-cache load) JAX reports."""

    def __init__(self):
        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event == _COMPILE_EVENT:
            self.times.append(time.perf_counter())


def run(ctx) -> DriverResult:
    from repro.dispatch import DispatchService
    from repro.kernels.spaces import kernel_space
    from repro.serve import PagedKVCache, make_serve_step, prefill

    tr, cfg = ctx.traffic, ctx.config
    dims = ref.Dims.from_config(cfg)
    arch = program_config(cfg)
    compiles = _Compiles()

    w = ref.make_weights(dims, ctx.seed_for("weights"), jnp.dtype(cfg["dtype"]))
    params = to_program(w)
    svc = DispatchService()    # as repro.launch.serve builds it: no store, no tuner
    page = int(kernel_space("decode_attention",
                            target=svc.target).default_configuration()["page"])
    clients, cap, round_cap = int(tr["clients"]), int(tr["max_batch"]), int(tr["round_cap"])
    prompt_set = loadgen.length_set(tr["prompt_lens"])
    out_set = loadgen.length_set(tr["output_lens"])
    max_len = max(prompt_set) + max(out_set)
    pc = PagedKVCache(arch, cap, max_len, page_size=page)
    serve = make_serve_step(arch, service=svc)
    stream = loadgen.request_stream(tr, ctx.seed_for("traffic"), dims.vocab)

    steps = {k: [] for k in ("t_s", "t_e", "batch", "active", "bucket", "keys",
                             "keys_active")}
    admits = {k: [] for k in ("t_a", "t_first", "t_b", "prompt_len")}
    state: dict[int, _Req] = {}
    done: list[_Req] = []

    def admit(slot: int, r: _Req) -> None:
        with ctx.annotate("bench.admit"):
            t_a = time.perf_counter()
            logits, cache = prefill(params, {"tokens": jnp.asarray(r.req.prompt)[None, :]},
                                    arch, max_len=pc.alloc, service=svc)
            first = int(np.asarray(jnp.argmax(logits[:, -1, :], axis=-1))[0])
            t_first = time.perf_counter()
            pc.admit(slot, cache, r.req.prompt_len)
            jax.block_until_ready(pc.buf)
            t_b = time.perf_counter()
        r.tokens.append(first)
        r.times.append(t_first)
        state[slot] = r
        for k, v in zip(admits, (t_a, t_first, t_b, r.req.prompt_len)):
            admits[k].append(v)

    # -- set-up: warm every shape the traffic uses, then fill the slots --------
    for P in prompt_set:
        logits, cache = prefill(params, {"tokens": jnp.zeros((1, P), jnp.int32)},
                                arch, max_len=pc.alloc, service=svc)
        np.asarray(jnp.argmax(logits[:, -1, :], axis=-1))
    pc.admit(0, cache, 1)
    pc.release(0)
    batch = ladder(min(clients, cap), cap)
    warm = list(range(batch))
    for bucket in range(page, pc.alloc + 1, page):
        view = pc.view(warm, bucket)
        nxt, _, view = serve(params, view, jnp.zeros((batch, 1), jnp.int32),
                             jnp.zeros((batch,), jnp.int32))
        np.asarray(nxt)
        pc.writeback(warm, bucket, view)
    jax.block_until_ready(pc.buf)
    t_fill = time.perf_counter()
    for slot in range(min(clients, cap)):
        admit(slot, _Req(next(stream), t_fill))
    waiting = [t_fill] * max(0, clients - cap)   # send times of queued clients

    # -- the window ------------------------------------------------------------
    t0 = ctx.open_window()
    t_end = t0 + ctx.seconds
    closed, cur = False, None
    while not closed:
        for slot in pc.free_slots():
            if not waiting or time.perf_counter() >= t_end:
                break
            admit(slot, _Req(next(stream), waiting.pop(0)))
        active = sorted(state)
        slots = active + [s for s in pc.free_slots()][: ladder(len(active), cap) - len(active)]
        n = min(round_cap, min(state[s].req.out_len - len(state[s].tokens) for s in active))
        bucket = pc.seq_bucket(slots, extra=n)
        with ctx.annotate("bench.round"):
            view = pc.view(slots, bucket)
            pos = np.array([pc.pos[s] + 1 if s in state else 0 for s in slots], np.int32)
            cur = jnp.asarray([[state[s].tokens[-1] if s in state else 0] for s in slots],
                              jnp.int32)
        for i in range(n):
            t_s = time.perf_counter()
            if t_s >= t_end:
                closed = True
                break
            with ctx.annotate("bench.decode_step"):
                nxt, _, view = serve(params, view, cur, jnp.asarray(pos + i))
                toks = np.asarray(nxt)[:, 0]
            t_e = time.perf_counter()
            for j, s in enumerate(active):
                state[s].tokens.append(int(toks[j]))
                state[s].times.append(t_e)
            pc.advance(active)
            cur = nxt
            # keys attended: row j's token at position pos[j] + i sees that many + 1
            na = len(active)
            for k, v in zip(steps, (t_s, t_e, len(slots), na, bucket,
                                    int(pos.sum()) + (i + 1) * len(slots),
                                    int(pos[:na].sum()) + (i + 1) * na)):
                steps[k].append(v)
            t_end += ctx.poll(t_e)
        if closed:
            break
        with ctx.annotate("bench.round"):
            pc.writeback(slots, bucket, view)
            for s in active:
                r = state[s]
                if len(r.tokens) >= r.req.out_len:
                    r.t_done = r.times[-1]
                    done.append(r)
                    pc.release(s)
                    del state[s]
                    waiting.append(r.t_done)
        closed = time.perf_counter() >= t_end
    ctx.close_window()

    # -- end-to-end metrics ----------------------------------------------------
    served = list(state.values()) + done
    n_tokens = sum(int(np.sum((np.asarray(r.times) >= t0) & (np.asarray(r.times) <= t_end)))
                   for r in served)
    gaps = [np.diff(t)[(t[:-1] >= t0) & (t[1:] <= t_end)]
            for t in (np.asarray(r.times) for r in served)]
    gaps = np.concatenate(gaps + [np.zeros(0)])
    # prompts whose prefill ended (first token out) in the window
    n_prompt = sum(r.req.prompt_len for r in served if t0 <= r.times[0] <= t_end)
    metrics = {"tokens_per_s": n_tokens / ctx.seconds,
               "prompt_tokens_per_s": n_prompt / ctx.seconds}
    if len(gaps):
        metrics["tpot_p99_ms"] = float(np.percentile(gaps, 99)) * 1e3
    mem = memory_peak_bytes()
    finished = [r for r in done if r.t_done <= t_end]
    attempted = len(finished) + len(state)

    # -- correctness: the plain reference over a sample of finished requests ---
    del view, cur, nxt, pc, serve, svc, params, logits, cache
    gc.collect()
    limits = load_json(os.path.join(ctx.bench_dir, "limits", f"{ctx.cell['name']}.json"))
    checks, readings = _check(ctx, w, dims, finished, max_len, limits)
    correct = all(np.isfinite(v) and v <= lim for v, lim in checks.values()) and bool(finished)

    records = {
        "t0": t0, "t_end": t_end, "trace_t0": ctx.trace_t0, "trace_t1": ctx.trace_t1,
        "steps": {k: np.asarray(v) for k, v in steps.items()},
        "admits": {k: np.asarray(v) for k, v in admits.items()},
        "compiles": np.asarray(compiles.times), "dims": dims, "readings": readings,
    }
    return DriverResult(window_t0=t0, attempted=attempted, failed=0, metrics=metrics,
                        records=records, checks=checks, correct=correct,
                        memory_peak_bytes=mem)


def _check(ctx, w, dims, finished, max_len, limits):
    """Widest gap of a served token's logit below the reference's best, over
    a seeded sample of finished requests with the longest among them. With
    ``ctx.control`` the control is judged in the system's place: at the same
    positions, the token that the float8 reference puts first. Returns the
    checks and both readings."""
    k = int(ctx.traffic["check"]["requests"])
    rng = np.random.default_rng(ctx.seed_for("check"))
    limit = float(limits["max_gap"]["limit"])
    if not finished:
        return {"max_gap": (float("inf"), limit)}, None
    order = sorted(finished, key=lambda r: -len(r.tokens))
    rest = order[1:]
    pick = [order[0]] + [rest[i] for i in sorted(rng.choice(len(rest), min(k - 1, len(rest)),
                                                            replace=False))]
    block = 256
    S = -(-max_len // block) * block
    worst, worst_c, n_tok = 0.0, 0.0, 0
    for r in pick:
        P, out = r.req.prompt_len, np.asarray(r.tokens, np.int32)
        seq = np.zeros(S, np.int32)
        seq[:P] = r.req.prompt
        seq[P:P + len(out)] = out
        nxt = jnp.asarray(np.concatenate([seq[1:], np.zeros(1, np.int32)]))
        at = slice(P - 1, P - 1 + len(out))
        best, picked, _ = ref.score(w, jnp.asarray(seq), nxt, dims=dims)
        worst = max(worst, float(np.max(np.asarray(best - picked)[at])))
        n_tok += len(out)
        if ctx.control:
            _, _, top_c = ref.score(w, jnp.asarray(seq), nxt, dims=dims, quant="fp8")
            _, picked_c, _ = ref.score(w, jnp.asarray(seq), top_c, dims=dims)
            worst_c = max(worst_c, float(np.max(np.asarray(best - picked_c)[at])))
    readings = {"program": worst, "control": worst_c if ctx.control else None,
                "tokens": n_tok, "requests": len(pick)}
    return {"max_gap": (worst_c if ctx.control else worst, limit)}, readings
