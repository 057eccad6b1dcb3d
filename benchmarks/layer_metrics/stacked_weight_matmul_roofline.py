"""Kernels, serve: the least time the chip could take to read the dense
model's four stacks of layer matrices once, which is what a decode step's
layer products must move, over the device time a step spent in the kernel
that reads them. The stacks' values (the family's
`ctx["counts"]["params"]`: every matrix-product parameter but the
embedding, `4 d d + 3 d f` a layer) x the bytes a value the replica holds
its weights in, over the chip's HBM bandwidth, against the summed device
time of the Pallas kernel's decode calls (`stacked_weight_matmul_decode`,
four a layer, a step; a prompt's calls run under another name) divided by
the trace's `decode_step` spans. Each matrix is read whole and once a
step, so the kernel is bound by those bytes; a step's rows in and out (16
rows a call) are a seventh of a percent of them and are left out. None
where the program has no such kernel (a tree whose layer products are
XLA's, a model of another family) or no trace."""

import re

from benchmarks.harness import flops

KERNEL = re.compile(r"^stacked_weight_matmul_decode")


def read(ctx):
    trace, peak = ctx.get("trace"), ctx.get("peak")
    if not trace or not peak:
        return None
    kernel_s = sum(s for name, s in trace["op_s"].items()
                   if KERNEL.match(name))
    span = trace.get("spans", {}).get("decode_step")
    params = ctx["counts"]["params"]
    if not kernel_s or not span or "embedding" not in params:
        return None
    value = ctx["counts"]["held"]["weights"]["bytes_per_value"]
    nbytes = (params["matmul"] - params["embedding"]) * value
    return (100.0 * flops.roofline_seconds(0.0, nbytes, peak)
            / (kernel_s / span["count"]))
