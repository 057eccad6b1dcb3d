"""Engine scheduler: handing the step's tokens to their streams and
retiring finished sequences (`engine.emit` spans: `_emit`, which wakes
each consumer, and `_maybe_finish`), a paged decode step."""

from benchmarks.harness import phases


def read(ctx):
    c = ctx["counters"]
    return phases.ms_per(c, phases.seconds(c, ["emit"]), "paged_steps")
