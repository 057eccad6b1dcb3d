"""Kernels, serve: the least time the chip could take for the traced
steps' attention over the SELECTED positions (the family's
`decode_attention_cost("selected", tokens)` over
`decode_kv_tokens_selected`: the chosen rows' keys and values are the
least a step must move) over the summed device time of the attention
body the model runs (`paged_decode_attention` under a keep mask, or
`sparse_paged_decode_attention`, one call a layer, a step). Both sides a
step, as `index_scores_roofline`. A masked walk fetches every live page,
four to eight times the chosen rows at this cell's lengths, so it reads
low: the share says what a fetch of the chosen rows alone could gain,
where `paged_decode_attention_roofline` holds the same time against the
pages the walk does move. None where the program has no such kernel,
counter or count."""

import re

from benchmarks.harness import flops

KERNEL = re.compile(r"^(paged_decode_attention"
                    r"|sparse_paged_decode_attention)")


def read(ctx):
    trace, counters, peak = (ctx.get("trace"), ctx.get("trace_counters"),
                             ctx.get("peak"))
    cost_of = ctx["counts"].get("decode_attention_cost")
    if not trace or not counters or not peak or not cost_of:
        return None
    kernel_s = sum(s for name, s in trace["op_s"].items()
                   if KERNEL.match(name))
    span = trace.get("spans", {}).get("decode_step")
    selected = counters.get("decode_kv_tokens_selected")
    steps = counters.get("decode_steps")
    layers = ctx.get("widths", {}).get("n_layers")
    if not kernel_s or not selected or not steps or not span or not layers:
        return None
    cost = cost_of("selected", selected / layers / steps)
    return (100.0 * flops.roofline_seconds(cost["flops"], cost["bytes"], peak)
            / (kernel_s / span["count"]))
