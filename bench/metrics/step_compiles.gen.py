"""Backend compiles (or persistent-cache loads) on the decode path in the
measured window: the program's ``jax.compile`` spans below a
``serve.step``, ``kv.view`` or ``kv.writeback`` span. A retrace of the
decode path; 0 once set-up has warmed every shape. Moves tpot_p99_ms."""

from bench.spans import ancestors, recorded, within

DECODE_PATH = {"serve.step", "kv.view", "kv.writeback"}


def read(run):
    rec = run.records
    spans = recorded(rec["t0"])
    if spans is None or not within(spans, "serve.step", rec["t0"], rec["t_end"]):
        return None
    by_id = {s.id: s for s in spans}
    return sum(1 for s in within(spans, "jax.compile", rec["t0"], rec["t_end"])
               if DECODE_PATH.intersection(ancestors(by_id, s)))
