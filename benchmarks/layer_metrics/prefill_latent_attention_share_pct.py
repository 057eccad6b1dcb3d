"""Kernels, serve: how much of a prompt's chunk the expanded latent
attention's forward is: the summed device time of the prefill's flash
forward (`flash_prefill_fwd_causal`, a call an MLA layer) over the
device-busy time inside the prefill programs (`jit_prefill*` on the
device's module line: in this cell `jit_prefill_chunk`), both over the
traced window. The gather of the earlier rows and their expansion into
keys and values a head are XLA's operations, which a device trace does
not name, and NOT in this share: with the delta-rule layers, the products
and the experts they are the rest. A change to the chunk's attention
moves this share and, through `prefill_share_of_window_pct`, the tokens a
second. Only a cell whose counts name a latent group reads it (another
model's causal forward is not this layer's); None there, where the trace
has no such kernel, or no prefill."""

import re

from benchmarks.harness import program_trace

KERNEL = re.compile(r"^flash_prefill_fwd_causal")


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not (ctx.get("counts") or {}).get("latent"):
        return None
    kernel_s = sum(s for name, s in trace.get("op_s", {}).items()
                   if KERNEL.match(name))
    reduction = program_trace.of_run(ctx)
    if not kernel_s or not reduction:
        return None
    device_s, runs = program_trace.module_seconds(reduction, "jit_prefill")
    return 100.0 * kernel_s / device_s if runs and device_s else None
