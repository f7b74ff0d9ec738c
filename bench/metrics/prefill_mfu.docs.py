"""Percent of the chip's bf16 peak that prefill reaches: the model FLOPs of
the prompts admitted in the measured window (flops.prefill_flops), over the
host time of those prefills, over the peak. Moves prompt_tokens_per_s."""

from bench import flops
from bench.readers import in_window


def read(run):
    ad, dims = run.records["admits"], run.records["dims"]
    sel = in_window(run.records, ad["t_a"], ad["t_first"])
    if not sel.any():
        return None
    f = sum(flops.prefill_flops(dims, int(p)) for p in ad["prompt_len"][sel])
    t = float((ad["t_first"][sel] - ad["t_a"][sel]).sum())
    return 100.0 * f / t / run.peaks["bf16_flops_per_s"]
