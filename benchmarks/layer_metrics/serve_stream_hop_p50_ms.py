"""Serve handle, router, replica: from the replica's `yield` of a token
(stamped by the benchmark's deployment class) to its receipt by the client
(same host, same clock). Median over every token of the window."""

from benchmarks.harness import stats


def read(ctx):
    return stats.percentile(ctx["client"]["hop_ms"], 50)
