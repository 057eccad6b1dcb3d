"""Experts: the share of the held experts that a layer of a decode step
reads at all. (Layer, expert) pairs with at least one token
(`moe_expert_touches`) over paged steps x expert layers x experts held,
in the window. An expert no token chose is not read: this share of the
experts' bytes is what a step moves."""


def read(ctx):
    c, moe = ctx["counters"], ctx["counts"].get("moe")
    if not moe or not c.get("paged_steps") \
            or c.get("moe_expert_touches") is None:
        return None
    return 100.0 * c["moe_expert_touches"] / (
        c["paged_steps"] * moe["layers"] * moe["experts_held"])
