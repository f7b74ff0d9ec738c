"""Percent of its roofline that the flash-attention kernel reaches in
prefill: the least time of the calls issued by the admissions of the traced
window (per layer, one call of batch * kv heads rows for each query head of
a group, over the prompt; memory bounds the shorter prompts, compute the
longest), over the
device time of every flash-attention call in the trace. Only prefill calls
the kernel. Moves prompt_tokens_per_s."""

from bench import flops
from bench.readers import in_trace, roofline_share


def read(run):
    ad, dims = run.records["admits"], run.records["dims"]
    sel = in_trace(run.records, ad["t_a"], ad["t_first"])
    K, G = dims.kv_heads, dims.heads // dims.kv_heads
    work = [flops.flash_attention_call(K, int(p), dims.head_dim)
            for p in ad["prompt_len"][sel]] * (dims.layers * G)
    return roofline_share(run, "flash_attention", None, work)
