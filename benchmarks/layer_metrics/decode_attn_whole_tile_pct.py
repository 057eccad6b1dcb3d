"""Kernels, serve: of the live pages the window's decode steps read
through the paged kernel (`decode_kv_pages_read`), the share read from a
pool held by planes (`decode_kv_pages_read_planes`): ``[blocks, layers,
slots x heads, block_size, values]``, where a layer's page is one
contiguous piece of whole tiles and the body forms one product a key
head (`ops.paged_attention.by_planes`). A model's group is held so
where its key/value heads do not fill a float32 tile of 8 sublanes
(`held_by_planes`, from the head count alone): 100 where every group
has 4, 0 where every group has 8 or 16, and in between the share of the
pages that a group of 4 holds. None where the program has no such
counter (a tree with one layout) or no step went through the kernel."""


def read(ctx):
    c = ctx.get("counters") or {}
    pages, planes = (c.get("decode_kv_pages_read"),
                     c.get("decode_kv_pages_read_planes"))
    if not pages or planes is None:
        return None
    return 100.0 * planes / pages
