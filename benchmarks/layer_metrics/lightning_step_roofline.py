"""Kernels, serve: the least time the chip could take to move the traced
steps' lightning states over the summed device time of the Pallas kernel
`lightning_decode_step` in the trace (one call a lightning layer, a
step). Bytes: the states of the rows the steps carried, read once and
written once (`lightning_state_bytes_moved`, counted by the model: rows x
lightning layers x 2 x a layer's ``[H, dk, dv]`` float32 state), over the
chip's memory bandwidth; operations: four a state value (decay, add,
multiply, sum), which at these widths are far under the bytes' time. The
kernel moves EVERY slot's state, a row's or not, so a step of 2 rows over
6 slots reads a third of what a full batch reads: the share says what the
rows needed, not what the kernel moved. None where the program has no
such kernel or counter (a tree without the model, a cell of another, the
step off the chip)."""

import re

from benchmarks.harness import flops

KERNEL = re.compile(r"^lightning_decode_step")


def read(ctx):
    trace, counters, peak = (ctx.get("trace"), ctx.get("trace_counters"),
                             ctx.get("peak"))
    if not trace or not counters or not peak:
        return None
    kernel_s = sum(s for name, s in trace.get("op_s", {}).items()
                   if KERNEL.match(name))
    moved = counters.get("lightning_state_bytes_moved")
    if not kernel_s or not moved:
        return None
    # 8 bytes moved a state value (read, written), 4 operations.
    return 100.0 * flops.roofline_seconds(moved / 2.0, moved, peak) / kernel_s
