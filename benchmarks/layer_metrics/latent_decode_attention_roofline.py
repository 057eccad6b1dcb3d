"""Kernels, serve: the least time the chip could take for the absorbed
latent attention of the traced decode steps over the summed device time
of the Pallas kernel `paged_latent_decode_attention` in the trace (one
call an MLA layer, a step). Bytes: the live latent pages the steps' block
tables named (`decode_latent_pages_read`, counted by the model for the
steps that went through the kernel) x the block size x a position's row
AS THE POOL HOLDS IT (whole planes of 128 lanes: the family's
`decode_attention_cost("latent", tokens)`, at the bytes a value the
replica holds), which are what the walk must move: a row is fetched once
for all query heads and its latent serves as key and as value.
Operations: every query head against the held row and the probabilities
against its latent; the larger of the two bounds is taken (the bytes at
these widths in bf16). None where the program has no such kernel, counter
or count (a tree without the model, a cell of another)."""

import re

from benchmarks.harness import flops

KERNEL = re.compile(r"^paged_latent_decode_attention")


def read(ctx):
    trace, counters, peak = (ctx.get("trace"), ctx.get("trace_counters"),
                             ctx.get("peak"))
    cost_of = (ctx.get("counts") or {}).get("decode_attention_cost")
    if not trace or not counters or not peak or not cost_of:
        return None
    kernel_s = sum(s for name, s in trace.get("op_s", {}).items()
                   if KERNEL.match(name))
    pages = counters.get("decode_latent_pages_read")
    if not kernel_s or not pages:
        return None
    cost = cost_of(
        "latent", pages * ctx["cell"]["settings"]["engine"]["block_size"])
    return 100.0 * flops.roofline_seconds(
        cost["flops"], cost["bytes"], peak) / kernel_s
