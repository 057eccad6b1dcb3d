"""Engine scheduler: of the decode steps of the window (`paged_steps`),
the share the scheduler dispatched before it had read the step before's
ids (`decode_steps_ahead`): the step took its tokens from those ids where
they lay on the device, and the ids' pickup and the host's turn between
two steps passed beside a busy device. The scheduler does so only behind
a batch whose every row is taken and none of whose rows is known to end
(no arrival could have been admitted in the turn it skips), so the share
is near the share of steps that ran at the batch's cap; an open loop
below its knee reads near 0. The rest ran as they always have: read,
then the host's turn, then the next dispatch. None where the program has
no such counter (it reads every step before it dispatches the next) or
the window ran no decode step."""


def read(ctx):
    c = ctx.get("counters") or {}
    ahead, steps = c.get("decode_steps_ahead"), c.get("paged_steps")
    if ahead is None or not steps:
        return None
    return 100.0 * ahead / steps
