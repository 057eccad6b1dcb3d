"""Kernels, train: the least time the chip could take for the flash
attention of the traced steps (forward and backward of every layer:
`flops.flash_attention_cost`, the larger of FLOPs over peak and bytes over
bandwidth) over the summed device time of the Pallas flash kernels in the
trace. Under remat the backward pass runs the forward kernel a second
time (its softmax statistics are not among the saved names); that second
run is time the kernels took and not work the algorithm needs, so it is in
the denominator only."""

import re

from benchmarks.harness import flops

# The Pallas MHA kernels of jax.experimental.pallas.ops.tpu.flash_attention
# as the device trace names them.
FLASH = re.compile(r"flash|_mha_|mha_forward|mha_backward", re.I)


def read(ctx):
    trace, counters, peak = ctx["trace"], ctx["trace_counters"], ctx["peak"]
    if not trace or not counters or not peak:
        return None
    kernel_s = sum(s for name, s in trace["op_s"].items()
                   if FLASH.search(name))
    if not kernel_s:
        return None
    w = ctx["widths"]
    cost = flops.flash_attention_cost(
        ctx["global_batch"] // ctx["chips"], w["n_heads"], ctx["seq_len"],
        w["d_model"] // w["n_heads"])
    least = flops.roofline_seconds(
        cost["flops"], cost["bytes"], peak) * w["n_layers"] * counters["steps"]
    return 100.0 * least / kernel_s
