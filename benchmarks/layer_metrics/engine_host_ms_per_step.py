"""Engine scheduler: wall time of the engine loop a decode step, outside
the device's own work and outside prefills: every phase of the loop
(`phase.*` of `InferenceEngine.stats()`) except the park, the waits for
the device (`*_wait`), and the prefill's phases (`prefill_*`,
`model_prefill_*`), over the window's paged decode steps. What is left is
reap, admit, capacity, tables, the decode call's padding and dispatch,
sample, emit, gauges and `other` (the loop's time in no named phase)."""

from benchmarks.harness import phases


def read(ctx):
    c = ctx["counters"]
    host = [n for n in phases.names(c)
            if n != "park" and not n.endswith("_wait")
            and not n.startswith(("prefill_", "model_prefill_"))]
    return phases.ms_per(c, phases.seconds(c, host), "paged_steps")
