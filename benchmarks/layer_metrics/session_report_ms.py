"""Trainer session: mean wall time of one `train.report` call (the host
read of the previous step's loss included), by the benchmark's clock."""


def read(ctx):
    c = ctx["counters"]
    if c["steps"] < 2:
        return None
    return c["report_s"] / (c["steps"] - 1) * 1e3
