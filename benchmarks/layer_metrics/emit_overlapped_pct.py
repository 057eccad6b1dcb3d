"""Engine scheduler: of the decode tokens of the window, the share the
engine handed to their streams from inside the next decode step's
`meanwhile`, between that step's dispatch and the wait for its ids, so
beside a busy device (`tokens_delivered_overlapped`), over the decode
tokens there were (`tokens_generated` less `prefills`: a prefill's token
goes out at once and is no decode token). The rest were flushed early,
beside an idle device: before a prefill's call, where nothing was left
running, or before a stream was ended from outside a step. A step's
tokens are counted as generated a step before they are delivered, so the
two window edges can move the share by a step's tokens in a window's.
None where the program has no such counter (it delivers inside the gap
between two steps) or no decode token was generated."""


def read(ctx):
    c = ctx.get("counters") or {}
    overlapped = c.get("tokens_delivered_overlapped")
    generated, prefills = c.get("tokens_generated"), c.get("prefills")
    if overlapped is None or generated is None or prefills is None:
        return None
    decoded = generated - prefills
    if decoded <= 0:
        return None
    return 100.0 * overlapped / decoded
