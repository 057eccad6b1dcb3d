"""Kernels, serve: the least time the chip could take for the window
layers' decode attention of the traced steps over the summed device time
of the Pallas kernel `paged_window_decode_attention` in the trace (one
call a sliding layer, a step). Bytes and operations: the family's
`decode_attention_cost("window", tokens)` over the live pages the steps'
window tables named (`decode_kv_pages_read_window`, counted by the model
for the steps that went through the kernel) x the block size. The kernel
reads whole pages, so the pages are the bytes it must move."""

import re

from benchmarks.harness import group_roofline

KERNEL = re.compile(r"^paged_window_decode_attention")


def read(ctx):
    return group_roofline.decode_attention(ctx, KERNEL, "window")
