"""Experts: the least time the chip could take to read the held experts
a traced decode step touched, over the device time a step spent in the
kernel that read them. (Layer, expert) pairs with at least one token
(`moe_expert_touches`, summed by the model over decode steps alone) x an
expert's three matrices (`ctx["counts"]["params"]["expert"]`) x the bytes
a value the replica holds its weights in, plus an expert layer's rows in
(the weights' dtype) and out (float32) where the family's widths name
`d_model`, over the chip's HBM bandwidth, against the summed device time
of the Pallas kernel `held_experts_ffn_decode` (one call an expert layer,
a step). Both sides a step: the counters' window begins and ends a
snapshot's time outside the traced one, so the bytes are divided by the
counters' decode steps and the time by the trace's `decode_step` spans.
A touched expert's weights are read whole and once, so the kernel is
bound by those bytes; its products are a batch's rows a tile. None where
the program has no such kernel (a tree whose expert layer is a scan of
XLA operations), no counter or no trace."""

import re

from benchmarks.harness import flops

KERNEL = re.compile(r"^held_experts_ffn_decode")


def read(ctx):
    trace, counters, peak = (ctx.get("trace"), ctx.get("trace_counters"),
                             ctx.get("peak"))
    if not trace or not counters or not peak:
        return None
    kernel_s = sum(s for name, s in trace["op_s"].items()
                   if KERNEL.match(name))
    span = trace.get("spans", {}).get("decode_step")
    touches = counters.get("moe_expert_touches")
    steps = counters.get("decode_steps")
    if not kernel_s or not touches or not steps or not span:
        return None
    counts = ctx["counts"]
    value = counts["held"]["weights"]["bytes_per_value"]
    rows = (counters.get("decode_rows", 0) * counts["moe"]["layers"]
            * ctx.get("widths", {}).get("d_model", 0) * (value + 4))
    nbytes = (touches * counts["params"]["expert"] * value + rows) / steps
    return (100.0 * flops.roofline_seconds(0.0, nbytes, peak)
            / (kernel_s / span["count"]))
