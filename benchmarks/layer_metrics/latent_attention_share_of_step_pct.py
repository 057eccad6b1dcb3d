"""Kernels, serve: how much of a decode step its latent attention is. The
summed device time of the Pallas kernel `paged_latent_decode_attention`
(one call an MLA layer, a step) over the device-busy time inside the
benchmark's `decode_step` spans, in the traced window. The kernel runs
inside decode steps alone (a prompt's attention is the expanded form
through the flash forward), so the quotient is a share of the step. At
one MLA layer in five beside 9.5 GB of weights it is small by
construction: the share says how small. None where the trace has no such
kernel (a tree or a cell without the model, the XLA body) or no step."""

import re

KERNEL = re.compile(r"^paged_latent_decode_attention")


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    span = trace.get("spans", {}).get("decode_step")
    kernel_s = sum(s for name, s in trace.get("op_s", {}).items()
                   if KERNEL.match(name))
    if not span or not span.get("device_busy_s") or not kernel_s:
        return None
    return 100.0 * kernel_s / span["device_busy_s"]
