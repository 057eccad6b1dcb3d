"""Model step, prefill: the share of the window the engine's loop spent
inside prefills (`prefill_s`, the clock of the `engine.prefill` span:
admission's table work, the model's prefill and the KV write), while
every running row waited. Listed in `BENCHMARK.json` for the cells whose
prompts are long enough for it to matter."""


def read(ctx):
    prefill_s = ctx["counters"].get("prefill_s")
    if prefill_s is None or not ctx.get("window_s"):
        return None
    return 100.0 * prefill_s / ctx["window_s"]
