"""Model step, prefill: the engine's host clock around prefill (compute,
the KV's trip to the host and back, the pool write) per thousand prompt
tokens the model computed."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("model.prefill_tokens"):
        return None
    return c["prefill_s"] / c["model.prefill_tokens"] * 1e6
