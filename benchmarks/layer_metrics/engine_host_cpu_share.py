"""Engine scheduler: CPU time of the engine loop's thread over the wall
time in which it had something to run: `thread_cpu_s` over `loop_s` less
the park and the waits for the device (`*_wait` phases). What is missing
to 100% is time the thread was runnable and did not run (the GIL) or
blocked in a call that is no wait by name (a transfer)."""

from benchmarks.harness import phases


def read(ctx):
    c = ctx["counters"]
    waits = ["park"] + [n for n in phases.names(c) if n.endswith("_wait")]
    waited = phases.seconds(c, waits)
    if waited is None or "thread_cpu_s" not in c or "loop_s" not in c:
        return None
    runnable = c["loop_s"] - waited
    return 100.0 * c["thread_cpu_s"] / runnable if runnable > 0 else None
