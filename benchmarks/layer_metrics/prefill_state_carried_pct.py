"""KV cache: of the chunks of prompts that began past position 0 in the
window (`prefill_later_chunks`, counted by the model a `prefill_chunk`
call), the share that began from their sequence's state slot
(`prefill_state_chunks`: the call was handed the slot and its program
read the delta-rule state and the convolution's tail there). It must be
100: a later chunk that began from nothing would prefill a different
sequence. Below 100 the chunk protocol dropped a slot on the way from the
cache to the model. None where the program has no such counters (a tree
or a model whose chunks carry no state) or no later chunk ran."""


def read(ctx):
    c = ctx.get("counters") or {}
    later, carried = (c.get("prefill_later_chunks"),
                      c.get("prefill_state_chunks"))
    if not later or carried is None:
        return None
    return 100.0 * carried / later
