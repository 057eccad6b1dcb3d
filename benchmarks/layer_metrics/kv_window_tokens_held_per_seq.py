"""KV cache: the positions a sequence holds in the window layer group
while the engine decodes. The window group's blocks in use, summed over
paged steps (`kv_window_block_steps_in_use`), times the block size, over
the rows of those steps (a decode row emits one token: tokens generated
less the prefills' first tokens). A window of `w` positions holds at most
``ceil(w / block_size) + 1`` blocks a sequence whatever its length (528
positions at 512 and blocks of 16): above that, blocks that left the
window are not being released."""


def read(ctx):
    c = ctx["counters"]
    rows = c.get("tokens_generated", 0) - c.get("prefills", 0)
    if not c.get("kv_window_block_steps") or rows <= 0:
        return None
    block = ctx["cell"]["settings"]["engine"]["block_size"]
    return c["kv_window_block_steps_in_use"] * block / rows
