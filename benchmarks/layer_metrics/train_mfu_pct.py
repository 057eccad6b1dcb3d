"""Train step: the model's FLOPs (for the dense block 6 per matmul
parameter and token plus causal attention; the family's
`ctx["counts"]["train_flops_per_token"]`; recomputation not counted) at
the tokens/s/chip of the traced steps, over the chip's bf16 peak from the
`device_kind` table. The traced steps, not the whole window
of the traced run: starting and stopping the profiler stalls the loop for
seconds (a third of the window on four chips). A device that is not in
the table has no MFU."""


def read(ctx):
    if not ctx["peak"]:
        return None
    rate = ctx["tokens_per_s_per_chip"]
    if ctx["trace"] and ctx["trace_counters"]:
        rate = (ctx["trace_counters"]["steps"] * ctx["global_batch"]
                * ctx["seq_len"] / ctx["trace"]["window_s"] / ctx["chips"])
    flops_per_token = ctx["counts"]["train_flops_per_token"](ctx["seq_len"])
    return (100.0 * flops_per_token * rate
            / ctx["peak"]["bf16_flops_per_s"])
