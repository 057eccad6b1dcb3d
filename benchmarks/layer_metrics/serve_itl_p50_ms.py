"""Client side: median gap between consecutive tokens of one request: a
decode step and the stream hop, with no prefill in between."""

from benchmarks.harness import stats


def read(ctx):
    return stats.percentile(ctx["client"]["gaps_ms"], 50)
