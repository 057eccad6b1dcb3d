"""Model step, decode: from the end of a decode step's last device
operation to the end of the `model.decode.logits_wait` span that holds
that instant, the device done and the loop without the ids yet: mean
over the traced window's steps that have such an interval, the device's
events first moved onto the host's clock (`stream_trace.py`, "The two
clocks": an upper bound, too long by a program's shortest launch)."""

from benchmarks.harness import stream_trace


def read(ctx):
    reduction = stream_trace.of_run(ctx)
    if not reduction or not reduction["pickups"]:
        return None
    return reduction["pickup_s"] / reduction["pickups"] * 1e3
