"""Kernels, serve: the least time one decode step could take over the
time the device was busy in one. Bytes a step must read (every weight
once, the live KV of its rows, each at the bytes a value the replica holds
it in; the family's `ctx["counts"]["decode_step_bytes"]`) over the
chip's HBM bandwidth, against the device-busy time inside the
benchmark's `decode_step` spans, per span, in the traced window. Decode
is bound by bytes; the FLOP side is taken too and the larger one used."""

from benchmarks.harness import flops


def read(ctx):
    trace, counters, peak = ctx["trace"], ctx["trace_counters"], ctx["peak"]
    if not trace or not counters or not peak:
        return None
    span = trace["spans"].get("decode_step")
    steps = counters.get("decode_steps")
    if not span or not steps or not span["device_busy_s"]:
        return None
    live = counters["decode_live_tokens"] / steps
    rows = counters["decode_rows"] / steps
    counts = ctx["counts"]
    least = flops.roofline_seconds(
        counts["decode_step_flops"](rows, live),
        counts["decode_step_bytes"](rows, live), peak)
    return 100.0 * least / (span["device_busy_s"] / span["count"])
