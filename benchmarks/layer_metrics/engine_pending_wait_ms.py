"""Engine scheduler: how long a decode token waits in `_pending`, from
the end of its step to the flush that hands it to its stream (as a rule
from inside the next step's `meanwhile`), mean over the window's
tokens."""


def read(ctx):
    c = ctx.get("counters") or {}
    if not c.get("pending_wait_tokens"):
        return None
    return c["pending_wait_s"] / c["pending_wait_tokens"] * 1e3
