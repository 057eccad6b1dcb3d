"""Kernels, serve: of the programs the dense model ran in the window
(prefills and decode steps, each of which runs every layer's four weight
products once), the share whose products were traced with
`ops.weight_matmul`'s Pallas kernel (`dense_steps_kernel`) and not with
XLA's product (`dense_steps_xla`). The kernel is chosen by what the code
observes (a TPU backend, whole lanes, a row count the sweep on the chip
says it wins at): 0 off the chip; on it 100 where every bucket the
traffic reaches is inside the row bound, the decode steps' share where a
prompt's bucket lies above it. None where the program has no such
counters (a tree whose layer products have one body) or ran no such
program (a model of another family)."""


def read(ctx):
    c = ctx.get("counters") or {}
    kernel, xla = c.get("dense_steps_kernel"), c.get("dense_steps_xla")
    if kernel is None or xla is None or not kernel + xla:
        return None
    return 100.0 * kernel / (kernel + xla)
