"""Kernels, serve: how much of a decode step the selecting attention's
three stages are: the summed device time of the index scores' kernel
(`paged_index_scores`, a call a layer), of the attention over the
selected positions (`paged_decode_attention`, the walk over every live
page under a keep mask, or `sparse_paged_decode_attention`, a fetch of
the chosen rows: whichever the model runs) and of the selection's
operations, over the device-busy time inside the benchmark's
`decode_step` spans, in the traced window. The selection is no kernel:
XLA runs its 32-step search as a `while` of a compare-and-count fusion,
and a device trace names such an operation `fusion.<n>` like any other,
so its time cannot be told from the step's other fusions by name; it is
NOT in this share (its size, measured alone on the chip: PERF.md,
Findings, PR 57). The attention's part alone is
`decode_attention_share_of_step_pct`. None where the trace has no such
kernel or no step."""

import re

KERNELS = re.compile(r"^(paged_index_scores|paged_decode_attention"
                     r"|sparse_paged_decode_attention)")


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    span = trace.get("spans", {}).get("decode_step")
    kernel_s = sum(s for name, s in trace.get("op_s", {}).items()
                   if KERNELS.match(name))
    if not span or not span.get("device_busy_s") or not kernel_s:
        return None
    return 100.0 * kernel_s / span["device_busy_s"]
