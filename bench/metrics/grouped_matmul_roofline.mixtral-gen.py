"""Percent of its roofline that the expert layer's grouped-matmul kernel
reaches in the serve step: the least time of the calls issued by the decode
steps of the traced window (bench/flops_moe.py: each reachable expert's
matrix read once, memory sets the bound at decode), over those calls'
device time in the trace. The kernel's ops carry the custom-call target
``tpu_custom_call``; the reading is given only where their count is the
traced steps times the layers times the calls a layer makes, so that no
other unnamed Pallas call is taken for it. Moves tokens_per_s."""

from bench import flops_moe
from bench.readers import in_trace, roofline_share


def read(run):
    st, dims = run.records["steps"], run.records["dims"]
    sel = in_trace(run.records, st["t_s"], st["t_e"])
    calls = int(sel.sum()) * dims.layers * flops_moe.GROUPED_MATMULS_PER_LAYER
    if run.trace is None or calls == 0 or \
            run.trace.count_of(flops_moe.GROUPED_MATMUL_LABEL, "serve_step") != calls:
        return None
    work = [c for b in st["active"][sel]
            for c in flops_moe.expert_layer_calls(dims, int(b))] * dims.layers
    return roofline_share(run, flops_moe.GROUPED_MATMUL_LABEL, "serve_step", work)
