"""Kernels, serve: how much of a decode step the walk over the chosen
pages is. The summed device time of the Pallas kernel
`block_sparse_paged_decode_attention` (one call a selecting layer and
key/value head, a step) over the device-busy time inside the decode
programs (`jit_decode_paged` on the device's module line), both over
the traced window. The module line and not the benchmark's `decode_step`
span: in a cell whose prompts go in chunks the span also holds the chunk
dispatched in front of the step (PERF.md 7, bt), which is five times a
step here. The kernel runs inside decode steps alone (a prompt's
attention is the flash forward under the block mask), so the quotient is
a share of the step. The selection before it (the compressed keys'
scores, the pooling, the threshold's search) is XLA's fusions, which a
device trace names `fusion.<n>` like any other, and NOT in this share
(its size by hand: PERF.md, Findings, PR 63). At two selecting layers of
eight beside 5 GB of weights the share is small by the model's design:
it says how small. None where the trace has no such kernel (a tree or a
cell without the model, the XLA body) or no step."""

import re

from benchmarks.harness import program_trace

KERNEL = re.compile(r"^block_sparse_paged_decode_attention")


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    kernel_s = sum(s for name, s in trace.get("op_s", {}).items()
                   if KERNEL.match(name))
    reduction = program_trace.of_run(ctx)
    if not kernel_s or not reduction:
        return None
    device_s, runs = program_trace.module_seconds(reduction,
                                                  "jit_decode_paged")
    return 100.0 * kernel_s / device_s if runs and device_s else None
