"""KV cache: what the pools hold a position beyond what the model counts
for it. The bytes of the live pages the window's decode steps named, as
the pools hold a position (`decode_kv_bytes_read_held`: whole slots of
the values' width, so a key of 192 values lies in one and a half slots
of 128), over the same pages' bytes as the model counts a position
(`decode_kv_bytes_read_model`: a key's and a value's own widths), less
one, both groups together, each weighed by what the steps read of it. 0
where keys and values are of one width; 20 where 384 values are held for
320. The padding is bytes a step reads and a pool keeps for nothing: the
rooflines count the model's bytes, so it shows there as time lost. None
where the program has no such counters (a tree without them, a model
whose attention is not read through the kernel)."""


def read(ctx):
    c = ctx.get("counters") or {}
    held, model = (c.get("decode_kv_bytes_read_held"),
                   c.get("decode_kv_bytes_read_model"))
    if not held or not model:
        return None
    return 100.0 * (held / model - 1.0)
