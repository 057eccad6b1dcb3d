"""Engine scheduler: the `np.argmax` over each row's logits
(`engine.sample` spans, decode steps and prefills), a paged decode
step."""

from benchmarks.harness import phases


def read(ctx):
    c = ctx["counters"]
    return phases.ms_per(c, phases.seconds(c, ["sample"]), "paged_steps")
