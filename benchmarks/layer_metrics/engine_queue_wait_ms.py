"""Engine scheduler: from a request's entry into the engine's waiting
queue to the start of its prefill, mean over the window's admissions
(`queue_wait_s`; one `engine.queue_wait` event an admission)."""


def read(ctx):
    c = ctx["counters"]
    if "queue_wait_s" not in c or not c.get("prefills"):
        return None
    return c["queue_wait_s"] / c["prefills"] * 1e3
