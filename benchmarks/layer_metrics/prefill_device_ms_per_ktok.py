"""Model step, prefill: device-busy time inside the runs of the prefill
programs (`jit_prefill`, `jit_prefill_cached`, `jit_prefill_paged` on
the device's module line) per 1,000 prompt tokens the model prefilled,
both over the traced window."""

from benchmarks.harness import program_trace


def read(ctx):
    reduction, counters = program_trace.of_run(ctx), ctx["trace_counters"]
    if not reduction or not counters:
        return None
    device_s, runs = program_trace.module_seconds(reduction, "jit_prefill")
    tokens = counters.get("model.prefill_tokens")
    return device_s / tokens * 1e6 if runs and tokens else None
