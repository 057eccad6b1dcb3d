"""Collectives: share of the traced window in which a collective was
running or in flight on device 0: all-reduce and friends on the op line,
and the asynchronous ring steps (collective-permute) that gathers and
scatters become on a mesh, on the async line. How much of it compute hid
is `collective_exposed_s` in the reduced trace, for the `tracing` issue."""


def read(ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    return 100.0 * trace["collective_s"] / trace["window_s"]
