"""Train step, compiles: programs compiled or fetched from the persistent
cache in the worker during the window (JAX's monitoring event). Must
read 0."""


def read(ctx):
    return ctx["counters"].get("compiles")
