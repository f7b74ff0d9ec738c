"""Device milliseconds of the expert layer's grouped-matmul kernel per
decode step of the traced window: the device time of its ops (custom-call
target ``tpu_custom_call``) in the serve step over the steps traced. Given
only where their count is the traced steps times the layers times the
calls a layer makes, as grouped_matmul_roofline.mixtral-gen reads them.
Moves tokens_per_s."""

from bench import flops_moe
from bench.readers import in_trace


def read(run):
    st, dims = run.records["steps"], run.records["dims"]
    n = int(in_trace(run.records, st["t_s"], st["t_e"]).sum())
    calls = n * dims.layers * flops_moe.GROUPED_MATMULS_PER_LAYER
    if run.trace is None or calls == 0 or \
            run.trace.count_of(flops_moe.GROUPED_MATMUL_LABEL, "serve_step") != calls:
        return None
    return 1e3 * run.trace.seconds_of(flops_moe.GROUPED_MATMUL_LABEL, "serve_step") / n
