"""Device, serve: of the traced window's device-idle time, the share
that lies inside the decode steps' pickup intervals (`stream_trace.py`:
the device done, the loop without the ids yet)."""

from benchmarks.harness import stream_trace


def read(ctx):
    reduction = stream_trace.of_run(ctx)
    if not reduction or not reduction["device_idle_s"]:
        return None
    return 100.0 * reduction["idle_in_pickup_s"] / reduction["device_idle_s"]
