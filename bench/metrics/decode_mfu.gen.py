"""Percent of the chip's bf16 peak: the model FLOPs of the tokens the
decode steps of the measured window made (flops.decoder_token_flops at each
token's context), over the window, over the peak. Moves tokens_per_s."""

from bench import flops
from bench.readers import in_window


def read(run):
    st, dims = run.records["steps"], run.records["dims"]
    sel = in_window(run.records, st["t_s"], st["t_e"])
    if not sel.any():
        return None
    n, ctx = int(st["active"][sel].sum()), int(st["keys_active"][sel].sum())
    f = n * flops.decoder_token_flops(dims, 0) + \
        4.0 * dims.layers * dims.heads * dims.head_dim * ctx
    return 100.0 * f / run.seconds / run.peaks["bf16_flops_per_s"]
