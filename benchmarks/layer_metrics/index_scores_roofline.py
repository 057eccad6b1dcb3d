"""Kernels, serve: the least time the chip could take for the traced
steps' index scores (the family's `index_scores_cost` over the positions
the indexer scored, `decode_index_tokens_scored`: their index keys' bytes
as the MODEL counts them, 64 values a key, and the 16 heads' products)
over the summed device time of the Pallas kernel `paged_index_scores`
(one call a layer, a step). Both sides a step: the counters' window
begins and ends a snapshot's time outside the traced one, so the cost is
divided by the counters' decode steps and the time by the trace's
`decode_step` spans. The pool holds a key in a row of 128 values, so the
kernel moves twice the bytes counted here: the share reads the padding
as time lost. None where the program has no such kernel, counter or
count."""

import re

from benchmarks.harness import flops

KERNEL = re.compile(r"^paged_index_scores")


def read(ctx):
    trace, counters, peak = (ctx.get("trace"), ctx.get("trace_counters"),
                             ctx.get("peak"))
    cost_of = ctx["counts"].get("index_scores_cost")
    if not trace or not counters or not peak or not cost_of:
        return None
    kernel_s = sum(s for name, s in trace["op_s"].items()
                   if KERNEL.match(name))
    span = trace.get("spans", {}).get("decode_step")
    scored = counters.get("decode_index_tokens_scored")
    steps = counters.get("decode_steps")
    layers = ctx.get("widths", {}).get("n_layers")
    if not kernel_s or not scored or not steps or not span or not layers:
        return None
    # The counter sums rows x layers; the cost is of all layers a position.
    cost = cost_of(scored / layers / steps)
    return (100.0 * flops.roofline_seconds(cost["flops"], cost["bytes"], peak)
            / (kernel_s / span["count"]))
