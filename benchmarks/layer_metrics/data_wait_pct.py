"""Data ingest: share of the window the train loop spent inside
`next(batches)` (`iter_jax_batches`), by the benchmark's own clock."""


def read(ctx):
    return 100.0 * ctx["counters"]["data_wait_s"] / ctx["window_s"]
