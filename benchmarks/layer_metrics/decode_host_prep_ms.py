"""Model step, decode: host time before the device can start a decode
step: the block tables (`engine.tables`), the padding and upload of the
step's inputs (`model.decode.prep`) and the call of the jitted function
(`model.decode.dispatch`), a paged decode step."""

from benchmarks.harness import phases


def read(ctx):
    c = ctx["counters"]
    return phases.ms_per(c, phases.seconds(
        c, ["tables", "model_decode_prep", "model_decode_dispatch"]),
        "paged_steps")
