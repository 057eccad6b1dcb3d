"""Serve handle, router, replica: from the engine's push of a token into
its `TokenStream` to the consumer's pickup in the replica, mean over the
window's tokens: the engine -> replica half of the stream hop
(`serve_stream_hop_p50_ms` is the replica -> client half)."""


def read(ctx):
    c = ctx["counters"]
    if "stream_wake_s" not in c or not c.get("stream_wake_tokens"):
        return None
    return c["stream_wake_s"] / c["stream_wake_tokens"] * 1e3
