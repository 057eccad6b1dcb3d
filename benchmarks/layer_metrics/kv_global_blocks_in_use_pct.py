"""KV cache: how full the global layer group's pool is while the engine
decodes. Its blocks in use, summed over paged steps
(`kv_global_block_steps_in_use`), over the blocks it has, summed likewise
(`kv_global_block_steps`). Only a model with a window group beside the
global one reports it (`kv_window_block_steps`): for the others the one
pool is every layer's."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("kv_window_block_steps") \
            or not c.get("kv_global_block_steps"):
        return None
    return (100.0 * c["kv_global_block_steps_in_use"]
            / c["kv_global_block_steps"])
