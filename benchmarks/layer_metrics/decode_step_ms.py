"""Model step, decode: the engine's own host clock around one paged
decode step (dispatch to logits on the host), mean over the window. Wall
time of a step, not device time."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("paged_steps"):
        return None
    return c["decode_s"] / c["paged_steps"] * 1e3
