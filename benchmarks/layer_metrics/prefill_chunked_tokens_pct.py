"""Engine scheduler: of the prompt tokens the model prefilled in the
window (`model.prefill_tokens`), the share that went through chunks
(`prefill_chunk_tokens`: the tokens of the `prefill_chunk` calls whose
rows reached the cache), a decode step of the running batch between two
of them, so that no running row waited behind more than one chunk. The
rest were prefilled whole, in one call behind which every running row
waited: a prompt of at most one chunk, and every prompt of a model
without the call (0 there). A chunk is counted where its rows are
stored and the model's tokens where it is called, so a window's edge
inside a chunk can move the share by one chunk's tokens in a window's.
None where the program has no such counter (it prefills every prompt
whole) or the window prefilled no token."""


def read(ctx):
    c = ctx.get("counters") or {}
    chunked, prefilled = (c.get("prefill_chunk_tokens"),
                          c.get("model.prefill_tokens"))
    if chunked is None or not prefilled:
        return None
    return 100.0 * chunked / prefilled
