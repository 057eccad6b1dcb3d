"""Serve handle, router, replica: mean `st:item.ack_wait` in the traced
window: how long a request's thread is held, blocked, until the owner
has answered the item's `generator_item` call and it can take its next
token: the round trip a token inside `stream_wake_ms` and the hop."""

from benchmarks.harness import stream_trace


def read(ctx):
    reduction = stream_trace.of_run(ctx)
    wait = (reduction or {"events": {}})["events"].get("item.ack_wait")
    if not wait or not wait["count"]:
        return None
    return wait["seconds"] / wait["count"] * 1e3
