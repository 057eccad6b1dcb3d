"""Collectives: share of the traced window in which a collective was
running or in flight on device 0 while no compute operation ran there:
the part of the communication that compute did not hide."""


def read(ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
