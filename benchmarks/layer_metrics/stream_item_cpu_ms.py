"""Serve handle, router, replica: time a streamed item costs its
request's thread in the process that produced it, as far as it has a
span on the profiler's clock: seconds of `st:item.submit` (the item is
packaged and handed to the IO loop) over the items of the traced window;
times the batch, the ms a step that compete with the engine loop. The IO
loop's share of an item (`item.rpc`) is in the program's ring alone."""

from benchmarks.harness import stream_trace


def read(ctx):
    reduction = stream_trace.of_run(ctx)
    if not reduction:
        return None
    seconds, items = stream_trace.item_seconds(reduction,
                                               stream_trace.ITEM_WORK)
    return seconds / items * 1e3 if items else None
