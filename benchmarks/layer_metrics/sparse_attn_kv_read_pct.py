"""Kernels, serve: of the positions live in the window's decode steps (a
row at position ``p`` has ``p + 1``; summed over rows and layers,
`decode_index_tokens_scored`: the indexer scores every one of them), the
share whose keys and values the attention's body fetched
(`decode_kv_tokens_read`). 100 where the body walks every live page and
masks what the indexer did not select; about 17 at this cell's lengths
the day it fetches the chosen rows alone. The share the model ATTENDS to
is `decode_kv_tokens_selected` over the same count (2,048 of 8k-16k a
row: 13-24%): what the step must read. None for a program without the
counters (a model whose attention selects nothing, the parent of the PR
that brought it)."""


def read(ctx):
    counters = ctx.get("counters") or {}
    live = counters.get("decode_index_tokens_scored")
    read_ = counters.get("decode_kv_tokens_read")
    if not live or read_ is None:
        return None
    return 100.0 * read_ / live
