"""KV cache: how full the per-sequence state pool is while the engine
decodes. State slots in use, summed over paged steps
(`state_slot_steps_in_use`), over the slots there are, summed likewise
(`state_slot_steps`): the engine keeps a slot a row of the batch and one
more, and a step reads and writes the whole pool."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("state_slot_steps"):
        return None
    return 100.0 * c["state_slot_steps_in_use"] / c["state_slot_steps"]
