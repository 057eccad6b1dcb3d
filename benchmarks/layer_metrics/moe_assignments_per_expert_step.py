"""Experts: tokens an expert held here takes in one layer of one decode
step, on average. (Token, expert) pairs that fell on held experts
(`moe_local_assignments`, counted inside the step) over paged steps x
expert layers x experts held, in the window. A deployment that spreads a
layer over n chips sends every chip's rows to these experts: n times
this at the same batch a chip."""


def read(ctx):
    c, moe = ctx["counters"], ctx["counts"].get("moe")
    if not moe or not c.get("paged_steps") \
            or c.get("moe_local_assignments") is None:
        return None
    return c["moe_local_assignments"] / (
        c["paged_steps"] * moe["layers"] * moe["experts_held"])
