"""KV cache: the prompt KV's trip from the device to the host
(`model.prefill.kv_d2h`) and back into the pool
(`engine.prefill.kv_write`: pad on the host, upload, scatter) per 1,000
prompt tokens prefilled."""

from benchmarks.harness import phases


def read(ctx):
    c = ctx["counters"]
    trip = phases.seconds(c, ["model_prefill_kv_d2h", "prefill_kv_write"])
    tokens = c.get("model.prefill_tokens")
    return trip / tokens * 1e6 if trip is not None and tokens else None
