"""Percent of the measured window spent admitting requests: prefill (its
forward, the cache replay and their compiles) and the copy into a slot.
Every decoding request waits meanwhile. A traced run's window is
``run.seconds`` of loop time; writing the trace out pauses the loop and
moves the window's end. Moves tpot_p99_ms."""

import numpy as np


def read(run):
    rec, ad = run.records, run.records["admits"]
    a = np.clip(ad["t_a"], rec["t0"], rec["t_end"])
    b = np.clip(ad["t_b"], rec["t0"], rec["t_end"])
    return 100.0 * float(np.sum(b - a)) / run.seconds
