"""Client side: median time from a request's due instant to its first token
through the streaming handle. Steadier than the judged tail, and where a
request that met no queue sits."""

from benchmarks.harness import stats


def read(ctx):
    return stats.percentile(ctx["client"]["ttft_ms"], 50)
