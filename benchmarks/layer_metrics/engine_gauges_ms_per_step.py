"""Engine scheduler: `_update_gauges` (the registry calls at the end of
every iteration; `engine.gauges` spans), a scheduler step."""

from benchmarks.harness import phases


def read(ctx):
    c = ctx["counters"]
    return phases.ms_per(c, phases.seconds(c, ["gauges"]), "steps")
