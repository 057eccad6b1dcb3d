"""Kernels, serve: the pages a fetch of the paged decode kernel brings.
The live pages the traced steps' block tables named
(`decode_kv_pages_read`) over the groups the kernel fetched them in
(`decode_kv_page_groups_read`, counted by the model on the host from the
same positions: a row's live pages in groups of the kernel's
`pages_per_step`, the last one short). Near the group size at long
contexts; near 1 where tables are a column or two wide and a grid step
cannot bring more. None where the program has no such counter (a tree
whose kernel brings a page a grid step) or no step went through the
kernel."""


def read(ctx):
    counters = ctx.get("trace_counters")
    if not counters:
        return None
    pages = counters.get("decode_kv_pages_read")
    groups = counters.get("decode_kv_page_groups_read")
    if not pages or not groups:
        return None
    return pages / groups
