"""The paged decode step over both pool residencies.

The KV block pool lives where the engine's model says (`kv_pool_ns`):
numpy for `TinyLM`, a jax array for `TransformerEngineModel`, whose
every mutation is a donated-arg jitted update. The engine hands the
pool + block tables into ONE model call per decode iteration (the
transformer's: attention over the pool's pages through the tables, one
layer at a time, decode math, in-place KV scatter). Correctness here is token-level: TinyLM's next token is a
function of the CACHED kv contents, so any table/gather/scatter
indexing bug changes the output against `TinyLM.oracle` (a subclass
that asks for `jax.numpy` puts the same oracle over the device pool);
the transformer tests compare against greedy full-recompute. COW,
adoption, preemption and cross-engine shipping semantics must be
bit-identical in both pool residencies.

Everything runs under `JAX_PLATFORMS=cpu` — the device pool is then
host RAM, but the code path (donation, reads through the tables, scatter
write-back) is what a TPU backend executes, but for the attention
itself: the chip's Pallas kernel is tested, interpreted, in
`test_ops_paged_attention.py`.
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


def _device_mgr(num_blocks, block_size=4, **kw):
    import jax.numpy as jnp

    return KVCacheManager(num_blocks=num_blocks, block_size=block_size,
                          kv_shape=KV, array_ns=jnp, **kw)


def _tinylm(pool, **kw):
    """`TinyLM` over its numpy pool (`host`), or the subclass that asks
    for `jax.numpy` (`device`): the exact oracle over the device pool's
    scatter, COW and adoption."""
    if pool == "host":
        return TinyLM(**kw)
    import jax.numpy as jnp

    class DeviceTinyLM(TinyLM):
        kv_pool_ns = jnp

    return DeviceTinyLM(**kw)


# ---------------------------------------------------------------------------
# device pool: manager-level storage semantics
# ---------------------------------------------------------------------------
def test_device_pool_write_gather_matches_numpy():
    """write / write_range spanning block boundaries through the
    donated scatter land exactly where the numpy pool puts them —
    including a range that starts and ends mid-block."""
    host = KVCacheManager(num_blocks=8, block_size=4, kv_shape=KV)
    dev = _device_mgr(8)
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
    mgr = _device_mgr(4, dtype=jnp.bfloat16)
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
    mgr = _device_mgr(8)
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


def test_paged_step_resolves_slots_and_rebinds_pool():
    """`paged_step` hands the model's fused step private (block, off)
    slots (COW backstop included), re-binds the donated pool it
    returns, and advances lens — the whole decode write path in one
    call."""
    mgr = _device_mgr(8)
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
    mgr = _device_mgr(4)
    assert mgr.allocate("s", 2)
    table = mgr.with_pool(lambda pool: mgr.block_table("s"))
    assert table == mgr.block_table("s")


# ---------------------------------------------------------------------------
# TinyLM: oracle-exact through the paged engine
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("pool", ["host", "device"])
def test_tinylm_paged_engine_matches_oracle(pool):
    """Paged decode (both pool residencies) reproduces TinyLM.oracle
    token-for-token, with zero host gathers."""
    m = _tinylm(pool, vocab_size=32)
    eng = InferenceEngine(m, EngineConfig(
        max_batch_size=4, block_size=4, num_blocks=64))
    prompts = [[1 + (i * 3 + j) % 20 for j in range(3 + i % 5)]
               for i in range(6)]
    streams = [eng.submit(p, 8) for p in prompts]
    _drive(eng)
    for p, s in zip(prompts, streams):
        assert s.tokens_so_far() == m.oracle(p, 8)
    st = eng.stats()
    assert st["paged"] and st["paged_steps"] > 0
    assert st["cache"]["host_gathers"] == 0
    assert st["cache"]["pool_residency"] == pool


def test_tinylm_paged_survives_preemption_and_adoption():
    """Tight cache forces preempt-requeue mid-generation and prefix
    sharing adopts blocks by reference — the paged read must still be
    oracle-exact afterwards (stale pool rows from freed blocks never
    leak through the block tables)."""
    m = _tinylm("device", vocab_size=32)
    eng = InferenceEngine(m, EngineConfig(
        max_batch_size=4, block_size=4, num_blocks=8,
        prefix_sharing=True))
    assert eng.cache.pool_residency == "device"
    base = [2, 4, 6, 8]
    prompts = [base + [10 + i] for i in range(4)]
    streams = [eng.submit(p, 6) for p in prompts]
    _drive(eng)
    for p, s in zip(prompts, streams):
        assert s.tokens_so_far() == m.oracle(p, 6)
    assert eng.preemptions > 0          # the tight cache actually bit
    assert eng.cache.host_gathers == 0


# ---------------------------------------------------------------------------
# transformer: paged == full recompute
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


def test_transformer_paged_matches_full_recompute(tiny_transformer):
    """The fused paged engine (device pool, pages read in place, in-place
    scatter) emits token-for-token what greedy full-forward recompute
    (`models.transformer.forward`, no cache) emits."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import forward

    params, cfg = tiny_transformer
    prompts = [[3, 17, 42, 9, 21, 5], [7, 7], [11, 23, 4, 50, 8, 9, 13]]
    _, eng = _transformer_engine(tiny_transformer)
    streams = [eng.submit(p, 6) for p in prompts]
    _drive(eng)
    assert eng.paged_steps > 0
    assert eng.cache.host_gathers == 0
    assert eng.cache.pool_residency == "device"
    for p, s in zip(prompts, streams):
        toks = s.tokens_so_far()
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
    _, src = _transformer_engine(tiny_transformer, prefix_sharing=True)
    src.submit(base + tail, 4)
    _drive(src)
    chunks, kvs = src.export_prefix(base)
    assert chunks and len(kvs) == len(chunks)

    _, dst = _transformer_engine(tiny_transformer, prefix_sharing=True)
    assert dst.import_prefix(chunks, kvs) == len(base)
    s_dst = dst.submit(base + tail, 4)
    _drive(dst)
    assert dst.prefix_hit_tokens >= len(base)   # adoption engaged

    _, ref = _transformer_engine(tiny_transformer)
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


@pytest.mark.parametrize("pool", ["host", "device"])
def test_engine_stats_surface_pool_and_phase_fields(pool):
    eng = InferenceEngine(_tinylm(pool, vocab_size=32), EngineConfig(
        max_batch_size=2, block_size=4, num_blocks=16))
    eng.submit([2, 3, 4], 4)
    _drive(eng)
    st = eng.stats()
    assert st["paged"] is True
    assert st["paged_steps"] == st["steps"] > 0
    cache = st["cache"]
    assert cache["pool_residency"] == pool
    assert cache["pool_bytes"] > 0
    assert cache["host_gathers"] == 0
    # Donated updates are the device pool's; a numpy pool is written in
    # place and counts none.
    assert (cache["pool_updates"] > 0) == (pool == "device")
    for key in ("kv_gather_s", "model_step_s", "decode_s",
                "jit_bucket_evictions"):
        assert key in st
    assert "kv_write_s" not in st


# ---------------------------------------------------------------------------
# one loop: the model says where its pool lives, nothing selects a path
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["tinylm", "tinylm_device", "transformer"])
def test_the_pools_residency_follows_the_model(kind, tiny_transformer):
    """No option places the pool: `TinyLM` gets numpy, a subclass that
    asks for `jax.numpy` and `TransformerEngineModel` get a jax array,
    under the same default `EngineConfig()`."""
    import jax

    if kind == "transformer":
        model, _ = _transformer_engine(tiny_transformer)
    else:
        model = _tinylm("device" if kind.endswith("device") else "host")
    eng = InferenceEngine(model, EngineConfig(block_size=4, num_blocks=8))
    want = "host" if kind == "tinylm" else "device"
    assert eng.cache.pool_residency == want
    assert eng.stats()["cache"]["pool_residency"] == want
    pool = eng.cache.with_pool(lambda pool: pool)
    assert isinstance(pool, np.ndarray if want == "host" else jax.Array)
    assert pool.shape == (8, 4) + tuple(model.kv_token_shape)


@pytest.mark.parametrize("kwargs, error", [
    ({"paged_decode": False}, ValueError),
    ({"device_pool": True}, TypeError),
    ({"kv_array_ns": np}, TypeError)],
    ids=["paged_decode_false", "device_pool", "kv_array_ns"])
def test_no_engine_option_selects_a_decode_loop_or_a_pool(kwargs, error):
    """`paged_decode` is kept as a name that accepts `True` (the
    benchmark's cell files pass it); the two pool options are gone."""
    with pytest.raises(error):
        EngineConfig(**kwargs)
    assert EngineConfig().paged_decode is True
    assert EngineConfig(paged_decode=True) == EngineConfig()


def test_default_engine_is_paged_through_no_partial_and_full_hit():
    """`EngineConfig()` over `TinyLM`: a cold prompt, a prompt that
    shares two sealed blocks (partial hit: `prefill_paged`) and the
    first prompt again (full hit: one read-only step) all emit the
    oracle's tokens, every decode step is a paged step, and the engine
    gathers no sequence's KV on the host."""
    m = TinyLM(vocab_size=32)
    eng = InferenceEngine(m, EngineConfig(block_size=4, num_blocks=32))
    base = [3, 5, 7, 9, 2, 4, 6, 8]              # two full blocks
    hits = []
    for prompt in (base, base + [11, 12, 13], base):
        before = eng.prefix_hit_tokens
        stream = eng.submit(prompt, 6)
        _drive(eng)
        assert stream.tokens_so_far() == m.oracle(prompt, 6)
        hits.append(eng.prefix_hit_tokens - before)
    assert hits == [0, 8, 8]
    st = eng.stats()
    assert st["paged"] is True
    assert st["paged_steps"] == st["steps"] == 15
    assert st["cache"]["host_gathers"] == 0
    # Two prefills ran the model's prefill; the full hit ran none.
    assert (m.prefill_calls, st["prefills"]) == (2, 3)


def test_transformer_compiles_only_its_three_programs(tiny_transformer,
                                                      caplog):
    """Over a run with sharing on (no hit, partial hit, full hit,
    decode), the model compiles `prefill`, `prefill_paged` and
    `decode_paged` and no other program of its own."""
    import re

    import jax

    from ray_tpu.serve.engine.model import _JitLRU

    model, eng = _transformer_engine(tiny_transformer)
    base = [3, 17, 42, 9, 21, 5, 11, 2]          # seals one 8-block
    with jax.log_compiles(), caplog.at_level("WARNING", logger="jax"):
        for prompt in (base + [33], base + [40, 41], base):
            eng.submit(prompt, 3)
            _drive(eng)
    assert eng.prefix_hit_tokens == 16
    compiled = set(re.findall(r"Finished XLA compilation of jit\((\w+)\)",
                              caplog.text))
    assert {n for n in compiled if n.startswith(("prefill", "decode"))} \
        == {"prefill", "prefill_paged", "decode_paged"}
    caches = {k for k, v in vars(model).items() if isinstance(v, _JitLRU)}
    assert caches == {"_prefill_jit", "_prefill_paged_jit",
                      "_decode_paged_jit"}
    assert model.jit_compiles == sum(len(getattr(model, k)) for k in caches)


def _all_avals(jaxpr):
    """Every value a jaxpr computes, inner jaxprs (scan, pjit) included."""
    import jax

    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield var.aval
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_avals(sub)


def test_decode_step_builds_no_dense_copy_of_the_batchs_cache(
        tiny_transformer):
    """The step reads the pool through the tables a layer at a time:
    besides the pool itself (the donated scatter's result), nothing it
    computes is as large as `[b_pad, s_pad, L, 2, H, hd]`, the whole
    cache of the batch; the largest read is one layer of it."""
    import jax
    import jax.numpy as jnp

    model, eng = _transformer_engine(tiny_transformer)
    b_pad, nb_pad, bs = 4, 8, eng.config.block_size
    layers, _, heads, hd = model.kv_token_shape
    pool = jax.ShapeDtypeStruct(
        (eng.config.num_blocks, bs) + model.kv_token_shape, jnp.float32)
    jaxpr = jax.make_jaxpr(model._build_decode_paged(b_pad, nb_pad, bs))(
        pool, model._params,
        jax.ShapeDtypeStruct((b_pad, 5 + nb_pad), jnp.int32),
        jax.ShapeDtypeStruct((model._ids_width(b_pad),), jnp.int32))
    one_layer = b_pad * nb_pad * bs * 2 * heads * hd
    sizes = sorted({int(np.prod(a.shape)) for a in _all_avals(jaxpr.jaxpr)
                    if a.shape != pool.shape})
    assert sizes[-1] == one_layer, sizes[-3:]
    assert one_layer * layers > sizes[-1]


def test_stats_carry_the_in_place_attention_counters(tiny_transformer):
    """`decode_attn_inplace_steps` and `decode_kv_pages_read` are
    top-level numbers of `stats()` from construction; on the CPU no
    step goes through the kernel, so both stand at 0 after a run."""
    _, eng = _transformer_engine(tiny_transformer)
    keys = ("decode_attn_inplace_steps", "decode_kv_pages_read")
    assert [eng.stats()[k] for k in keys] == [0, 0]
    eng.submit([3, 17, 42, 9], 5)
    _drive(eng)
    assert eng.stats()["paged_steps"] > 0
    assert [eng.stats()[k] for k in keys] == [0, 0]
    tiny = InferenceEngine(TinyLM(), EngineConfig(
        max_batch_size=2, block_size=4, num_blocks=8))
    assert [tiny.stats()[k] for k in keys] == [0, 0]


def test_a_model_without_the_paged_protocol_is_refused_at_construction():
    class GatheredOnlyLM:
        """The old host-gather protocol: `prefill` and `decode`."""
        kv_token_shape = (1,)
        prefill = TinyLM.prefill
        decode = TinyLM.decode

    with pytest.raises(ValueError, match="decode_paged, prefill_paged"):
        InferenceEngine(GatheredOnlyLM())


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
