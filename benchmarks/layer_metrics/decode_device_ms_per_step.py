"""Model step, decode: device-busy time inside the runs of the paged
decode program (`jit_decode_paged` on the device's module line), a run,
in the traced window."""

from benchmarks.harness import program_trace


def read(ctx):
    reduction = program_trace.of_run(ctx)
    if not reduction:
        return None
    device_s, runs = program_trace.module_seconds(reduction,
                                                  "jit_decode_paged")
    return device_s / runs * 1e3 if runs else None
