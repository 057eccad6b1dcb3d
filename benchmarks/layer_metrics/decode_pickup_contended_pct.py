"""Serve handle, router, replica: of the pickup intervals' time
(`stream_trace.py`), the share during which at least one other thread of
the replica process is inside an `st:` work event (`item.ack_wait` is a
wait and does not count): which thread ran while the engine loop waited
for a step's ids. Prints the table it rests on, a line of its own before
the result line: the pickup intervals, the stream path's union, and per
`st:` event the count, the seconds and the seconds inside pickup
intervals; with the seconds the reducer's child took."""

import json

from benchmarks.harness import stream_trace


def read(ctx):
    reduction = stream_trace.of_run(ctx)
    if not reduction:
        return None
    table = {name: [e["count"], round(e["seconds"], 6),
                    round(e["in_pickup_s"], 6)]
             for name, e in reduction["events"].items()}
    rest = {k: round(v, 6) if isinstance(v, float) else v
            for k, v in reduction.items() if k != "events"}
    print("stream path [count, seconds, in_pickup_s]: " + json.dumps(table)
          + " pickups: " + json.dumps(rest), flush=True)
    if not reduction["pickup_s"]:
        return None
    return 100.0 * reduction["contended_s"] / reduction["pickup_s"]
