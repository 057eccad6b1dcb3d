"""Kernels, serve: how much of a prompt's chunk the selecting attention's
two kernels are: the summed device time of the chunk's index scores
(`prefill_index_scores`, a call a layer) and of the forward under the
per-query mask (`flash_prefill_fwd_selected`, a call a layer) over the
device-busy time inside the prefill programs (`jit_prefill*` on the
device's module line: in this cell `jit_prefill_chunk`), both over the
traced window. The threshold's 32-step search between the two is XLA's
fusions, which a device trace does not name (as the decode step's
selection: `sparse_attention_share_of_step_pct`), and NOT in this share:
with the gather of the earlier rows, the products and the experts it is
the rest. A change to the chunk's attention moves this share and,
through `prefill_share_of_window_pct`, the tokens a second; the cell
reports no time to the first token. None where the trace has no such
kernel (a tree or a cell whose prompts select nothing) or no prefill."""

import re

from benchmarks.harness import program_trace

KERNELS = re.compile(r"^(prefill_index_scores|flash_prefill_fwd_selected)")


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    kernel_s = sum(s for name, s in trace.get("op_s", {}).items()
                   if KERNELS.match(name))
    reduction = program_trace.of_run(ctx)
    if not kernel_s or not reduction:
        return None
    device_s, runs = program_trace.module_seconds(reduction, "jit_prefill")
    return 100.0 * kernel_s / device_s if runs and device_s else None
