"""Device-resident paged decode: donated KV pool + in-jit block gather.

Unit tier for PR 20. The KV block pool can live as a jax array
(`KVCacheManager(device_pool=True)`) whose every mutation is a
donated-arg jitted update, and the engine's paged path
(`EngineConfig(paged_decode=True)`) hands the pool + block tables into
ONE fused compiled step per decode iteration (in-jit `jnp.take`
gather, decode math, in-place KV scatter). Correctness here is
token-level: TinyLM's next token is a function of the CACHED kv
contents, so any table/gather/scatter indexing bug changes the output
against `TinyLM.oracle`; the transformer tests compare against the
host-gather engine AND greedy full-recompute. COW, adoption,
preemption and cross-engine shipping semantics must be bit-identical
in both pool residencies.

Everything runs under `JAX_PLATFORMS=cpu` — the device pool is then
host RAM, but the code path (donation, in-jit gather, scatter
write-back) is exactly what a TPU backend executes.
"""

import threading

import numpy as np
import pytest

from ray_tpu.serve.engine import (EngineConfig, InferenceEngine,
                                  KVCacheManager, TinyLM)

pytestmark = pytest.mark.unit

KV = (2, 3)          # toy per-token KV shape for manager-level tests


def _drive(eng):
    while eng.step():
        pass


# ---------------------------------------------------------------------------
# device pool: manager-level storage semantics
# ---------------------------------------------------------------------------
def test_device_pool_write_gather_matches_numpy():
    """write / write_range spanning block boundaries through the
    donated scatter land exactly where the numpy pool puts them —
    including a range that starts and ends mid-block."""
    host = KVCacheManager(num_blocks=8, block_size=4, kv_shape=KV)
    dev = KVCacheManager(num_blocks=8, block_size=4, kv_shape=KV,
                         device_pool=True)
    assert dev.pool_residency == "device"
    vals = np.arange(11 * 6, dtype=np.float32).reshape(11, *KV)
    for mgr in (host, dev):
        assert mgr.allocate("s", 11)
        mgr.write_range("s", 0, vals[:3])       # head, mid-block end
        mgr.write_range("s", 3, vals[3:10])     # spans two boundaries
        mgr.write("s", 10, vals[10])            # single-token write
    np.testing.assert_array_equal(np.asarray(dev.gather("s")),
                                  host.gather("s"))
    np.testing.assert_array_equal(np.asarray(dev.gather("s", 5)),
                                  vals[:5])
    assert dev.pool_updates >= 3
    assert dev.pool_bytes == 8 * 4 * 6 * 4      # blocks*size*kv*fp32


def test_device_pool_bfloat16_roundtrip():
    """A bfloat16 pool stores and gathers with bf16 rounding only —
    the dtype a TPU-resident pool would actually use."""
    jnp = pytest.importorskip("jax.numpy")
    mgr = KVCacheManager(num_blocks=4, block_size=4, kv_shape=KV,
                         dtype=jnp.bfloat16, device_pool=True)
    assert mgr.allocate("s", 6)
    vals = np.linspace(0.0, 2.0, 6 * 6, dtype=np.float32).reshape(
        6, *KV)
    mgr.write_range("s", 0, vals)
    out = np.asarray(mgr.gather("s"), np.float32)
    np.testing.assert_allclose(out, vals, atol=0.01)   # bf16 mantissa
    assert mgr.pool_bytes == 4 * 4 * 6 * 2
    assert mgr.stats()["pool_residency"] == "device"


def test_device_pool_cow_privatizes_before_write():
    """A write into a shared block on the device pool copies it first:
    the writer sees its new value, the other holder keeps reading the
    original bytes."""
    mgr = KVCacheManager(num_blocks=8, block_size=4, kv_shape=KV,
                         device_pool=True)
    assert mgr.allocate("a", 4)
    vals = np.ones((4,) + KV, np.float32)
    mgr.write_range("a", 0, vals)
    shared = mgr.block_table("a")[0]
    mgr.adopt("b", [shared], 4)
    mgr.write("b", 2, vals[0] * 7.0)            # COW fault
    assert mgr.block_table("b")[0] != shared
    assert mgr.cow_copies == 1
    np.testing.assert_array_equal(np.asarray(mgr.gather("a")), vals)
    got = np.asarray(mgr.gather("b"))
    np.testing.assert_array_equal(got[2], vals[0] * 7.0)
    np.testing.assert_array_equal(got[:2], vals[:2])


@pytest.mark.parametrize("device_pool", [False, True])
def test_write_step_batched_one_token_writes(device_pool):
    """`write_step` lands row i of a padded [b_pad, *kv] batch at
    entry i's slot; padding rows are dropped (device: scattered out of
    range), and shared blocks privatize first."""
    mgr = KVCacheManager(num_blocks=8, block_size=4, kv_shape=KV,
                         device_pool=device_pool)
    assert mgr.allocate("a", 3) and mgr.allocate("b", 6)
    base = np.zeros((6,) + KV, np.float32)
    mgr.write_range("a", 0, base[:2])
    mgr.write_range("b", 0, base)
    batch = np.zeros((4,) + KV, np.float32)     # b_pad=4, 2 live rows
    batch[0] = 11.0
    batch[1] = 22.0
    batch[2:] = 99.0                            # must never land
    mgr.write_step([("a", 2), ("b", 5)], batch)
    assert mgr.seq_len("a") == 3 and mgr.seq_len("b") == 6
    np.testing.assert_array_equal(np.asarray(mgr.gather("a"))[2],
                                  batch[0])
    np.testing.assert_array_equal(np.asarray(mgr.gather("b"))[5],
                                  batch[1])
    assert not np.any(np.asarray(mgr.gather("b"))[:5] == 99.0)


def test_paged_step_resolves_slots_and_rebinds_pool():
    """`paged_step` hands the model's fused step private (block, off)
    slots (COW backstop included), re-binds the donated pool it
    returns, and advances lens — the whole decode write path in one
    call."""
    mgr = KVCacheManager(num_blocks=8, block_size=4, kv_shape=KV,
                         device_pool=True)
    assert mgr.allocate("a", 4)
    vals = np.ones((4,) + KV, np.float32)
    mgr.write_range("a", 0, vals)
    shared = mgr.block_table("a")[0]
    mgr.adopt("b", [shared], 4)
    assert mgr.allocate("b", 5)                 # room for the step

    seen = {}

    def fused(pool, blocks, offs):
        # stand-in for the model's donated jit: write one row eagerly
        seen["slots"] = (list(blocks), list(offs))
        new = pool.at[blocks[0], offs[0]].set(5.0)
        return "logits", new

    out = mgr.paged_step([("b", 4)], fused)
    assert out == "logits"
    assert mgr.seq_len("b") == 5
    # The written slot was private: COW split "b" off the shared block
    # chain only if the target block was shared (pos 4 lives in b's
    # second block, freshly allocated, so no copy needed here).
    blk, off = seen["slots"][0][0], seen["slots"][1][0]
    assert (blk, off) == (mgr.block_table("b")[1], 0)
    got = np.asarray(mgr.gather("b"))
    assert got[4].flat[0] == 5.0
    np.testing.assert_array_equal(got[:4], vals)   # adopted head intact


def test_with_pool_is_reentrant():
    """`with_pool` callbacks may call public accessors (the scheduler's
    paged prefill reads tables while holding the pool) — the cache lock
    is reentrant."""
    mgr = KVCacheManager(num_blocks=4, block_size=4, kv_shape=KV,
                         device_pool=True)
    assert mgr.allocate("s", 2)
    table = mgr.with_pool(lambda pool: mgr.block_table("s"))
    assert table == mgr.block_table("s")


# ---------------------------------------------------------------------------
# TinyLM: oracle-exact through the paged engine
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("device_pool", [False, True])
def test_tinylm_paged_engine_matches_oracle(device_pool):
    """Paged decode (both pool residencies) reproduces TinyLM.oracle
    token-for-token, with zero host gathers."""
    m = TinyLM(vocab_size=32)
    eng = InferenceEngine(m, EngineConfig(
        max_batch_size=4, block_size=4, num_blocks=64,
        paged_decode=True, device_pool=device_pool))
    prompts = [[1 + (i * 3 + j) % 20 for j in range(3 + i % 5)]
               for i in range(6)]
    streams = [eng.submit(p, 8) for p in prompts]
    _drive(eng)
    for p, s in zip(prompts, streams):
        assert s.tokens_so_far() == m.oracle(p, 8)
    st = eng.stats()
    assert st["paged"] and st["paged_steps"] > 0
    assert st["cache"]["host_gathers"] == 0
    assert st["cache"]["pool_residency"] == (
        "device" if device_pool else "host")


def test_tinylm_paged_survives_preemption_and_adoption():
    """Tight cache forces preempt-requeue mid-generation and prefix
    sharing adopts blocks by reference — the paged read must still be
    oracle-exact afterwards (stale pool rows from freed blocks never
    leak through the block tables)."""
    m = TinyLM(vocab_size=32)
    eng = InferenceEngine(m, EngineConfig(
        max_batch_size=4, block_size=4, num_blocks=8,
        paged_decode=True, device_pool=True, prefix_sharing=True))
    base = [2, 4, 6, 8]
    prompts = [base + [10 + i] for i in range(4)]
    streams = [eng.submit(p, 6) for p in prompts]
    _drive(eng)
    for p, s in zip(prompts, streams):
        assert s.tokens_so_far() == m.oracle(p, 6)
    assert eng.preemptions > 0          # the tight cache actually bit
    assert eng.cache.host_gathers == 0


# ---------------------------------------------------------------------------
# transformer: paged == host-gather == full recompute
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_transformer():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig, init_params

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                            n_heads=2, d_ff=64, max_seq_len=128,
                            dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return params, cfg


def _transformer_engine(tiny_transformer, **cfg_kw):
    from ray_tpu.serve.engine import TransformerEngineModel

    params, cfg = tiny_transformer
    model = TransformerEngineModel(params, cfg, max_batch_size=4)
    return model, InferenceEngine(model, EngineConfig(
        max_batch_size=4, block_size=8, num_blocks=24, **cfg_kw))


def test_transformer_paged_matches_host_and_full_recompute(
        tiny_transformer):
    """The fused paged engine (device pool, in-jit gather, in-place
    scatter) emits token-for-token what the host-gather engine emits —
    and both match greedy full-forward recompute."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import forward

    params, cfg = tiny_transformer
    prompts = [[3, 17, 42, 9, 21, 5], [7, 7], [11, 23, 4, 50, 8, 9, 13]]
    outs = []
    for paged in (False, True):
        _, eng = _transformer_engine(tiny_transformer,
                                     paged_decode=paged)
        streams = [eng.submit(p, 6) for p in prompts]
        _drive(eng)
        outs.append([s.tokens_so_far() for s in streams])
        if paged:
            assert eng.paged_steps > 0
            assert eng.cache.host_gathers == 0
            assert eng.cache.pool_residency == "device"
    assert outs[0] == outs[1]
    for p, toks in zip(prompts, outs[1]):
        seq, oracle = list(p), []
        for _ in range(6):
            lg, _ = forward(params, jnp.asarray([seq], jnp.int32), cfg)
            t = int(np.argmax(np.asarray(lg)[0, -1]))
            oracle.append(t)
            if t == 1:          # engine eos_token
                break
            seq.append(t)
        assert toks == oracle


def test_transformer_sharing_paged_matches_unshared(tiny_transformer):
    """Adoption + paged prefill-from-pool + COW over the real
    transformer: sharing on (paged) == sharing off (paged) — the
    in-jit prefix gather reads exactly what the prefill wrote."""
    base = [3, 17, 42, 9, 21, 5, 11, 2]         # seals one 8-block
    reqs = [(base + [33], 4), (base + [40], 4), (base + [33], 4)]
    outs = []
    for sharing in (False, True):
        _, eng = _transformer_engine(tiny_transformer,
                                     paged_decode=True,
                                     prefix_sharing=sharing)
        streams = []
        for p, n in reqs:       # staged: block seals before next admit
            streams.append(eng.submit(p, n))
            _drive(eng)
        outs.append([s.tokens_so_far() for s in streams])
        assert eng.cache.host_gathers == 0
        if sharing:
            assert eng.prefix_hit_tokens >= 16
    assert outs[0] == outs[1]


def test_transformer_ship_then_paged_decode_parity(tiny_transformer):
    """Cross-engine prefix shipping into a device pool: blocks exported
    from one paged engine and installed into another's jnp pool
    (`read_block`/`install_block` crossing residency) decode to the
    same tokens as computing locally."""
    base = [3, 17, 42, 9, 21, 5, 11, 2]
    tail = [33, 40]
    _, src = _transformer_engine(tiny_transformer, paged_decode=True,
                                 prefix_sharing=True)
    src.submit(base + tail, 4)
    _drive(src)
    chunks, kvs = src.export_prefix(base)
    assert chunks and len(kvs) == len(chunks)

    _, dst = _transformer_engine(tiny_transformer, paged_decode=True,
                                 prefix_sharing=True)
    assert dst.import_prefix(chunks, kvs) == len(base)
    s_dst = dst.submit(base + tail, 4)
    _drive(dst)
    assert dst.prefix_hit_tokens >= len(base)   # adoption engaged

    _, ref = _transformer_engine(tiny_transformer, paged_decode=True)
    s_ref = ref.submit(base + tail, 4)
    _drive(ref)
    assert s_dst.tokens_so_far() == s_ref.tokens_so_far()


# ---------------------------------------------------------------------------
# jit bucket caches + stats surface
# ---------------------------------------------------------------------------
def test_jit_lru_caps_buckets_and_counts_evictions():
    from ray_tpu.serve.engine.model import _JitLRU

    lru = _JitLRU(2)
    lru[1] = "a"
    lru[2] = "b"
    assert lru.get(1) == "a"        # refreshes 1
    lru[3] = "c"                    # evicts 2 (LRU)
    assert len(lru) == 2 and lru.evictions == 1
    assert lru.get(2) is None and lru.get(1) == "a"


def test_transformer_jit_cache_cap_evicts_and_reports(tiny_transformer):
    """A tiny cap forces compiled-bucket evictions under varied shapes;
    the model reports them (`jit_cache_evictions`) and the engine
    surfaces the sum in stats for the counter metric."""
    from ray_tpu.serve.engine import TransformerEngineModel

    params, cfg = tiny_transformer
    model = TransformerEngineModel(params, cfg, max_batch_size=4,
                                   jit_cache_cap=1)
    eng = InferenceEngine(model, EngineConfig(
        max_batch_size=2, block_size=8, num_blocks=24))
    for p, n in (([3], 3), ([4, 5] * 5, 4), ([6] * 20, 5)):
        eng.submit(p, n)
    _drive(eng)
    assert model.jit_cache_evictions > 0
    assert eng.stats()["jit_bucket_evictions"] == \
        model.jit_cache_evictions


def test_engine_stats_surface_pool_and_phase_fields():
    m = TinyLM(vocab_size=32)
    eng = InferenceEngine(m, EngineConfig(
        max_batch_size=2, block_size=4, num_blocks=16,
        paged_decode=True))
    eng.submit([2, 3, 4], 4)
    _drive(eng)
    st = eng.stats()
    assert st["paged"] is True
    assert st["paged_steps"] > 0
    cache = st["cache"]
    assert cache["pool_residency"] == "device"
    assert cache["pool_bytes"] > 0
    assert cache["host_gathers"] == 0
    assert cache["pool_updates"] > 0
    for key in ("kv_gather_s", "model_step_s", "kv_write_s",
                "jit_bucket_evictions"):
        assert key in st


# ---------------------------------------------------------------------------
# asked for the device path: get it or raise
# ---------------------------------------------------------------------------
def test_device_pool_without_jax_raises_not_degrades(monkeypatch):
    """`device_pool=True` used to fall back to a numpy pool when jax
    could not be imported; a serving replica would then run off the
    device without a word."""
    import sys

    monkeypatch.setitem(sys.modules, "jax.numpy", None)  # import fails
    with pytest.raises(ImportError):
        KVCacheManager(num_blocks=4, block_size=4, kv_shape=KV,
                       device_pool=True)


def test_paged_decode_on_model_without_paged_support_raises():
    class HostOnlyLM(TinyLM):
        supports_paged = False

    with pytest.raises(ValueError, match="supports_paged"):
        InferenceEngine(HostOnlyLM(), EngineConfig(paged_decode=True))
    # The host-gather loop still takes such a model.
    assert not InferenceEngine(HostOnlyLM(), EngineConfig()).paged


def test_step_failure_outside_decode_fails_streams_and_is_logged(caplog):
    """An exception out of `step()` (a compile error on the chip, say)
    used to be swallowed by the hosting loop: streams hung forever."""
    eng = InferenceEngine(TinyLM(vocab_size=32), EngineConfig(
        max_batch_size=2, block_size=4, num_blocks=16))

    def boom():
        raise RuntimeError("xla said no")

    eng._ensure_capacity = boom
    stream = eng.submit([3, 4, 5], 4)
    seen = []

    def consume():
        try:
            seen.extend(stream)
        except RuntimeError as e:
            seen.append(e)

    consumer = threading.Thread(target=consume, daemon=True)
    with caplog.at_level("ERROR"):
        eng.start()
        try:
            consumer.start()
            consumer.join(timeout=10)
        finally:
            eng.stop()
    assert not consumer.is_alive(), "the stream hung"
    assert isinstance(seen[-1], RuntimeError) and "xla said no" in str(
        seen[-1]), seen
    assert any("engine step failed" in r.message for r in caplog.records)
