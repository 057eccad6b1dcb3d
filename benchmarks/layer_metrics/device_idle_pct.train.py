"""Device, train: share of the traced window in which no operation ran,
averaged over the chips used."""


def read(ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
