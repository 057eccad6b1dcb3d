"""KV cache: share of the window's prompt-KV writes into the pool that
went from the prefill's device output to the pool's scatter without a
host copy (`prefill_kv_device_writes` against `prefill_kv_host_writes`
of `InferenceEngine.stats()`). 100 with a device pool; a program without
the counters, or a window without a prefill, gives nothing."""


def read(ctx):
    c = ctx["counters"]
    on_device = c.get("prefill_kv_device_writes")
    on_host = c.get("prefill_kv_host_writes")
    if on_device is None or on_host is None or not on_device + on_host:
        return None
    return 100.0 * on_device / (on_device + on_host)
