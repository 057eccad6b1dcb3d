"""Engine scheduler: of the chunks of prompts whose rows reached the cache
in the window (`prefill_chunks`), the share the host did not wait for
(`prefill_chunks_unwaited`, counted by the model where it skips the
wait: every chunk that is not its prompt's last hands the host nothing,
so the call returns once the chunk is dispatched, and its rows' write
and the batch's decode step go out behind it: the host's turn between a
chunk and its step passes beside a busy device). A prompt's last chunk
yields the logits of its first token and is read, so the share is 1 − 1
÷ (chunks a prompt): 88-93 at 7-15 chunks a prompt, 50-89 at 2-9. The
model counts a chunk where it is dispatched and the engine where its
rows are stored, so a window's edge between the two moves the share by
one chunk in a window's. None where the program has no such counter (it
waits for every chunk) or the window ran no chunk (a model without the
call, prompts of at most one chunk)."""


def read(ctx):
    c = ctx.get("counters") or {}
    unwaited, chunks = (c.get("prefill_chunks_unwaited"),
                        c.get("prefill_chunks"))
    if unwaited is None or not chunks:
        return None
    return 100.0 * unwaited / chunks
