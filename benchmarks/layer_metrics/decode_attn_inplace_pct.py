"""Kernels, serve: of the paged decode steps in the window, the share
whose attention read the KV pool's pages where they lie, through the
Pallas kernel (`decode_attn_inplace_steps`, which the model counts a step
it dispatched with the kernel's body: `ops.paged_attention.
kernel_eligible` said yes to every layer kind's widths), over
`paged_steps`. 100 on the chip for the widths the kernel takes; below it
the cell times the XLA body, which gathers a dense copy of the batch's
KV a layer. None where the program has no such counter or no paged step
ran."""


def read(ctx):
    c = ctx.get("counters") or {}
    steps, inplace = c.get("paged_steps"), c.get("decode_attn_inplace_steps")
    if not steps or inplace is None:
        return None
    return 100.0 * inplace / steps
