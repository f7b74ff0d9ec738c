"""Percent of its roofline that the decode-attention kernel reaches in the
serve step: the least time of the calls issued by the decode steps of the
traced window (every valid key and value read once; memory sets the bound
at these shapes), over those calls' device time in the trace. Moves
tokens_per_s."""

from bench import flops
from bench.readers import in_trace, roofline_share


def read(run):
    st, dims = run.records["steps"], run.records["dims"]
    sel = in_trace(run.records, st["t_s"], st["t_e"])
    K, G = dims.kv_heads, dims.heads // dims.kv_heads
    work = [flops.decode_attention_call(int(b) * K, G, dims.head_dim, int(k) * K)
            for b, k in zip(st["batch"][sel], st["keys"][sel])] * dims.layers
    return roofline_share(run, "decode_attention", "serve_step", work)
