"""Median host time of a prefill, from the admission's start to its first
token on the host, over the admissions of the measured window. Moves
prompt_tokens_per_s."""

import numpy as np

from bench.readers import in_window


def read(run):
    ad = run.records["admits"]
    sel = in_window(run.records, ad["t_a"], ad["t_first"])
    if not sel.any():
        return None
    return 1e3 * float(np.median(ad["t_first"][sel] - ad["t_a"][sel]))
