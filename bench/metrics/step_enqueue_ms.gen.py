"""Median host time to launch one decode step: the program's
``serve.step`` spans of the measured window, which end when the step is
enqueued, before its tokens are awaited. Moves tokens_per_s."""

import numpy as np

from bench.spans import recorded, within


def read(run):
    rec = run.records
    spans = recorded(rec["t0"])
    if spans is None:
        return None
    steps = within(spans, "serve.step", rec["t0"], rec["t_end"])
    if not steps:
        return None
    return 1e-6 * float(np.median([s.end_ns - s.start_ns for s in steps]))
