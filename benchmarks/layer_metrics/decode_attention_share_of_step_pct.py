"""Kernels, serve: how much of a decode step its attention is. The summed
device time of the two paged attention kernels (`paged_decode_attention`,
a call a global layer, and `paged_window_decode_attention`, a call a
window layer) over the device-busy time inside the benchmark's
`decode_step` spans, in the traced window. The kernels run inside decode
steps alone (a prefill's attention is the flash forward), so the quotient
is a share of the step. None where the trace has no such kernel (a tree
or a cell whose decode attention is the XLA body) or no step."""

import re

KERNELS = re.compile(r"^paged_(window_)?decode_attention")


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
