"""KV cache: calls that materialised a sequence's KV on the host. Must
read 0 on the paged path."""


def read(ctx):
    return ctx["counters"].get("cache.host_gathers")
