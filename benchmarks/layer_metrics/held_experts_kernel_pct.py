"""Experts: of the programs a sparse-expert model ran in the window
(prefills and decode steps, each of which runs every expert layer once,
so the share of programs is the share of expert layer-steps), the share
whose expert layers were traced with `ops.experts`' Pallas kernel
(`moe_steps_kernel`) and not its scan of XLA operations
(`moe_steps_scan`). The kernel is chosen by what the code observes (a
TPU backend, a batch of one tile, whole lanes): 0 off the chip; on it
the decode steps' share, a prompt of more rows than a tile keeping the
scan. None where the program has no such counters (a tree whose expert
layer has one body, a model without experts)."""


def read(ctx):
    c = ctx.get("counters") or {}
    kernel, scan = c.get("moe_steps_kernel"), c.get("moe_steps_scan")
    if kernel is None or scan is None or not kernel + scan:
        return None
    return 100.0 * kernel / (kernel + scan)
