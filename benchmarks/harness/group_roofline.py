"""The roofline share of one layer group's decode attention, for the
readers of a model that keeps its KV by layer group (one reader file a
group, as `BENCHMARK.json` names a metric a file)."""

from benchmarks.harness import flops


def decode_attention(ctx: dict, kernel, group: str):
    """The least time the chip could take for `group`'s decode attention
    of the traced steps (the family's `decode_attention_cost` over the
    live pages the steps' tables of that group named, x the block size)
    over the summed device time of the operations `kernel` matches; None
    where the program has no such kernel, counter or count."""
    trace, counters, peak = ctx["trace"], ctx["trace_counters"], ctx["peak"]
    cost_of = ctx["counts"].get("decode_attention_cost")
    if not trace or not counters or not peak or not cost_of:
        return None
    kernel_s = sum(s for name, s in trace["op_s"].items()
                   if kernel.match(name))
    pages = counters.get(f"decode_kv_pages_read_{group}")
    if not kernel_s or not pages:
        return None
    cost = cost_of(
        group, pages * ctx["cell"]["settings"]["engine"]["block_size"])
    return 100.0 * flops.roofline_seconds(
        cost["flops"], cost["bytes"], peak) / kernel_s
