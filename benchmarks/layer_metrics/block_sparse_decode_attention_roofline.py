"""Kernels, serve: the least time the chip could take for the decode
attention over the CHOSEN pages of the traced steps over the summed
device time of the Pallas kernel `block_sparse_paged_decode_attention`
in the trace (one call a selecting layer and key/value head, a step).
Bytes: the cached positions of the pages the steps' selections chose
(`decode_kv_tokens_read`, counted by the model a (row, layer, key/value
head) from the selection's rule) x a head's key and value (the family's
`decode_attention_cost("block_sparse", tokens)`, at the bytes a value the
replica holds), which are what the walk must move. Operations: the
group's 16 query heads against the keys and the probabilities against the
values; the larger of the two bounds is taken (the bytes at these widths
in bf16). The compressed keys' bytes are NOT in it: the selection's
products read them (XLA's fusions, which no metric reads), not this kernel.
None where the program has no such kernel, counter or count (a tree
without the model, a cell of another, the XLA body)."""

import re

from benchmarks.harness import flops

KERNEL = re.compile(r"^block_sparse_paged_decode_attention")


def read(ctx):
    trace, counters, peak = (ctx.get("trace"), ctx.get("trace_counters"),
                             ctx.get("peak"))
    cost_of = (ctx.get("counts") or {}).get("decode_attention_cost")
    if (not trace or not counters or not peak or not cost_of
            or "block_sparse" not in (ctx.get("counts") or {})):
        return None
    kernel_s = sum(s for name, s in trace.get("op_s", {}).items()
                   if KERNEL.match(name))
    tokens = counters.get("decode_kv_tokens_read")
    if not kernel_s or not tokens:
        return None
    cost = cost_of("block_sparse", tokens)
    return 100.0 * flops.roofline_seconds(
        cost["flops"], cost["bytes"], peak) / kernel_s
