"""Kernels, serve: the least time the chip could take for the decode
attention of the traced steps over the summed device time of the Pallas
kernel `paged_decode_attention` in the trace (one call a layer that
keeps KV, a step). Bytes: the live pages the steps' block tables named
(`decode_kv_pages_read`, counted by the model for the steps that went
through the kernel) x the page's bytes over all layers that keep KV
(block size x the family's `kv_bytes_per_token`, at the bytes a value the
replica holds). Operations: the family's `decode_step_flops` at no rows,
which leaves the scores and values over that many cached tokens. The
kernel reads whole pages, so the pages are the bytes it must move."""

import re

from benchmarks.harness import flops

KERNEL = re.compile(r"^paged_decode_attention")


def read(ctx):
    trace, counters, peak = ctx["trace"], ctx["trace_counters"], ctx["peak"]
    if not trace or not counters or not peak:
        return None
    kernel_s = sum(s for name, s in trace["op_s"].items()
                   if KERNEL.match(name))
    pages = counters.get("decode_kv_pages_read")
    if not kernel_s or not pages:
        return None
    counts = ctx["counts"]
    tokens = pages * ctx["cell"]["settings"]["engine"]["block_size"]
    least = flops.roofline_seconds(
        counts["decode_step_flops"](0, tokens),
        tokens * counts["kv_bytes_per_token"], peak)
    return 100.0 * least / kernel_s
