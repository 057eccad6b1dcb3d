"""Engine scheduler: sequences in a decode step, on average. Tokens that
came out of decode steps (all generated, less the one each prefill
yields) over paged steps, in the window."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("paged_steps"):
        return None
    return (c["tokens_generated"] - c["prefills"]) / c["paged_steps"]
