"""Load generator (the benchmark's own): how late after its due time a
request was actually sent. A starved generator must not be read as a fast
server."""

from benchmarks.harness import stats


def read(ctx):
    return stats.percentile(ctx["client"]["late_ms"], 95)
