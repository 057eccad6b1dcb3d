"""Kernels, serve: as `window_decode_attention_roofline`, for the full
layers of a model that keeps its KV by layer group: the family's
`decode_attention_cost("global", tokens)` over the live pages the steps'
global tables named (`decode_kv_pages_read_global`) against the summed
device time of the Pallas kernel `paged_decode_attention` (one call a
full layer, a step; the windowed kernel runs under another name)."""

import re

from benchmarks.harness import group_roofline

KERNEL = re.compile(r"^paged_decode_attention")


def read(ctx):
    return group_roofline.decode_attention(ctx, KERNEL, "global")
