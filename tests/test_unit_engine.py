"""Continuous-batching engine: scheduler + KV-cache unit tier.

Seconds-fast, in-process, no sockets: the engine's `step()` is driven
directly (no thread), TinyLM is deterministic and cache-exercising (its
next token is a function of the CACHED kv contents, so any block-table
bug changes the output), and `TinyLM.oracle` is the no-cache reference
the engine must reproduce through admission, preemption-requeue and
retirement.
"""

import threading
import time

import numpy as np
import pytest

from ray_tpu.serve.engine import (CacheOverflowError, EngineConfig,
                                  EngineOverloadedError, InferenceEngine,
                                  KVCacheManager, TinyLM)

pytestmark = pytest.mark.unit


# ---------------------------------------------------------------------------
# KV-cache manager
# ---------------------------------------------------------------------------
def test_kv_block_accounting_and_atomic_alloc():
    mgr = KVCacheManager(num_blocks=4, block_size=4, kv_shape=(1,))
    assert mgr.capacity_tokens == 16
    assert mgr.allocate("a", 5)            # 2 blocks
    assert mgr.free_blocks() == 2
    assert mgr.utilization() == pytest.approx(0.5)
    # Growing within the allocated blocks is free.
    assert mgr.allocate("a", 8)
    assert mgr.free_blocks() == 2
    # Atomic failure: asking for 3 more blocks with 2 free changes
    # NOTHING.
    assert not mgr.allocate("b", 12)
    assert mgr.free_blocks() == 2
    assert mgr.block_table("b") == []
    # A fitting allocation still works, then free returns everything.
    assert mgr.allocate("b", 8)
    assert mgr.free_blocks() == 0
    assert mgr.free("a") == 2
    assert mgr.free_blocks() == 2
    assert mgr.free("a") == 0              # double free is a no-op


def test_kv_write_gather_through_blocks():
    mgr = KVCacheManager(num_blocks=8, block_size=3, kv_shape=(2,))
    assert mgr.allocate("s", 7)            # 3 blocks, non-contiguous ok
    vals = np.arange(14, dtype=np.float32).reshape(7, 2)
    mgr.write_range("s", 0, vals[:5])      # bulk prefill write
    mgr.write("s", 5, vals[5])             # per-step writes
    mgr.write("s", 6, vals[6])
    out = mgr.gather("s")
    np.testing.assert_array_equal(out, vals)
    # Partial gather (the decode view at an earlier position).
    np.testing.assert_array_equal(mgr.gather("s", 4), vals[:4])
    assert mgr.seq_len("s") == 7


def test_kv_write_without_block_raises_and_overflow():
    mgr = KVCacheManager(num_blocks=2, block_size=2, kv_shape=())
    with pytest.raises(IndexError):
        mgr.write("s", 0, 1.0)             # nothing allocated
    with pytest.raises(CacheOverflowError):
        mgr.allocate("s", 5)               # > capacity: never satisfiable


def test_kv_blocks_are_reused_after_free():
    mgr = KVCacheManager(num_blocks=2, block_size=2, kv_shape=())
    assert mgr.allocate("a", 4)
    mgr.write_range("a", 0, np.array([1.0, 2.0, 3.0, 4.0], np.float32))
    mgr.free("a")
    assert mgr.allocate("b", 4)
    mgr.write_range("b", 0, np.array([9.0, 8.0, 7.0, 6.0], np.float32))
    np.testing.assert_array_equal(
        mgr.gather("b"), np.array([9.0, 8.0, 7.0, 6.0], np.float32))


def test_kv_write_range_spans_block_boundaries():
    """A bulk write starting mid-block and crossing several blocks
    lands every value at its logical position (start offset != 0,
    crossing two boundaries, ending mid-block)."""
    mgr = KVCacheManager(num_blocks=8, block_size=4, kv_shape=(2,))
    assert mgr.allocate("s", 11)           # 3 blocks
    vals = np.arange(22, dtype=np.float32).reshape(11, 2)
    mgr.write_range("s", 0, vals[:3])      # fill part of block 0
    # Start at offset 3 of block 0, cross blocks 1 and 2, end at
    # offset 2 of block 2.
    mgr.write_range("s", 3, vals[3:11])
    np.testing.assert_array_equal(mgr.gather("s"), vals)
    assert mgr.seq_len("s") == 11
    # A range that would run past the allocated table is rejected and
    # everything up to the last allocated position was still written.
    with pytest.raises(IndexError):
        mgr.write_range("s", 10, np.zeros((4, 2), np.float32))


def test_kv_allocate_at_exact_capacity():
    """The == edges: one sequence taking every block succeeds; one
    token more can never be satisfied (overflow, not False); and with
    zero free blocks a second allocation fails atomically."""
    mgr = KVCacheManager(num_blocks=4, block_size=4, kv_shape=())
    assert mgr.allocate("a", 16)           # exactly the whole cache
    assert mgr.free_blocks() == 0
    assert mgr.allocate("a", 16)           # idempotent at the edge
    with pytest.raises(CacheOverflowError):
        mgr.allocate("a", 17)              # > capacity: unsatisfiable
    assert not mgr.allocate("b", 1)        # full: atomic False
    assert mgr.block_table("b") == []
    mgr.free("a")
    assert mgr.allocate("b", 16)           # exact fit after free
    with pytest.raises(CacheOverflowError):
        mgr.can_allocate("c", 17) or mgr.allocate("c", 17)


def test_kv_gather_golden_equal_to_per_position_reference():
    """The vectorized gather (precomputed per-sequence index arrays)
    is value-identical to a naive per-position table walk, across
    interleaved allocations, frees and partial lengths."""
    rng = np.random.default_rng(7)
    mgr = KVCacheManager(num_blocks=16, block_size=3, kv_shape=(2,))
    written = {}
    for seq, n in (("a", 7), ("b", 10), ("c", 5)):
        assert mgr.allocate(seq, n)
        vals = rng.standard_normal((n, 2)).astype(np.float32)
        mgr.write_range(seq, 0, vals)
        written[seq] = vals
    mgr.free("b")                          # fragment the free list
    assert mgr.allocate("d", 8)
    vals = rng.standard_normal((8, 2)).astype(np.float32)
    mgr.write_range("d", 0, vals)
    written["d"] = vals

    def reference(seq, length):
        table = mgr.block_table(seq)
        out = np.zeros((length, 2), np.float32)
        for pos in range(length):
            out[pos] = mgr._buffer[table[pos // mgr.block_size],
                                   pos % mgr.block_size]
        return out

    for seq in ("a", "c", "d"):
        n = mgr.seq_len(seq)
        np.testing.assert_array_equal(mgr.gather(seq), reference(seq, n))
        np.testing.assert_array_equal(mgr.gather(seq, n - 2),
                                      reference(seq, n - 2))


# ---------------------------------------------------------------------------
# iteration-level scheduling
# ---------------------------------------------------------------------------
def _drive(engine, max_steps=10000):
    steps = 0
    while engine.step():
        steps += 1
        assert steps < max_steps, "engine failed to converge"
    return steps


def test_engine_matches_oracle_mixed_batch():
    m = TinyLM()
    eng = InferenceEngine(m, EngineConfig(max_batch_size=4, block_size=4,
                                          num_blocks=64))
    reqs = [([5, 9, 3], 6), ([2, 2], 3), ([7], 9), ([4, 4, 4, 4], 1),
            ([11, 3], 5)]
    streams = [eng.submit(p, n) for p, n in reqs]
    _drive(eng)
    for (p, n), s in zip(reqs, streams):
        assert s.tokens_so_far() == m.oracle(p, n)
        assert s.finished
    # Everything retired: every block is either free or held ONLY by
    # the prefix index (sealed prompt blocks stay adoptable), and
    # releasing the index returns the cache to empty.
    idx = eng.prefix_index
    assert (eng.cache.free_blocks()
            == eng.cache.num_blocks - idx.held_blocks())
    idx.release_all()
    assert eng.cache.free_blocks() == eng.cache.num_blocks


def test_eos_stops_generation_early():
    m = TinyLM(eos_period=5)
    eng = InferenceEngine(m, EngineConfig(block_size=4, num_blocks=32))
    prompts = [[3, 4], [6], [9, 9, 9]]
    streams = [eng.submit(p, 20) for p in prompts]
    _drive(eng)
    for p, s in zip(prompts, streams):
        oracle = m.oracle(p, 20)
        assert s.tokens_so_far() == oracle
        if m.eos_token in oracle:
            assert oracle[-1] == m.eos_token
            assert len(oracle) < 20


def test_continuous_batching_shorts_finish_during_long_decode():
    """THE property: with one long and many short requests in flight,
    every short completes while the long one is still decoding — no
    request waits for a batch-mate."""
    m = TinyLM()
    eng = InferenceEngine(m, EngineConfig(max_batch_size=4, block_size=4,
                                          num_blocks=64))
    long_stream = eng.submit([3, 3, 3], 60)
    shorts = [eng.submit([4 + i], 3) for i in range(6)]
    short_done_steps = {}
    steps = 0
    while eng.step():
        steps += 1
        for i, s in enumerate(shorts):
            if s.finished and i not in short_done_steps:
                short_done_steps[i] = steps
        assert steps < 10000
    # All shorts finished strictly before the long request...
    assert len(short_done_steps) == 6
    long_total_steps = steps
    assert max(short_done_steps.values()) < long_total_steps
    # ...even the ones admitted AFTER the long one filled a batch slot
    # (a static batcher would hold them to the long pole).
    assert max(short_done_steps.values()) <= 6 * 3 + 10
    assert long_stream.tokens_so_far() == m.oracle([3, 3, 3], 60)
    for i, s in enumerate(shorts):
        assert s.tokens_so_far() == m.oracle([4 + i], 3)


def test_static_policy_holds_batch_to_completion():
    """The @serve.batch-shaped baseline: batches form at FULL width
    (not serial size-1 decoding), then hold to completion — later
    arrivals wait for the whole first batch, costing MORE steps for
    the same tokens."""
    m1, m2 = TinyLM(), TinyLM()
    reqs = [([3, 3, 3], 24)] + [([4 + i], 3) for i in range(6)]

    cont = InferenceEngine(m1, EngineConfig(
        max_batch_size=4, block_size=4, num_blocks=64))
    streams = [cont.submit(p, n) for p, n in reqs]
    cont_steps = _drive(cont)
    for (p, n), s in zip(reqs, streams):
        assert s.tokens_so_far() == m1.oracle(p, n)

    stat = InferenceEngine(m2, EngineConfig(
        max_batch_size=4, block_size=4, num_blocks=64,
        policy="static"))
    streams = [stat.submit(p, n) for p, n in reqs]
    peak = 0
    batch2_started_before_batch1_done = False
    stat_steps = 0
    while stat.step():
        stat_steps += 1
        occ = stat.batch_occupancy()
        peak = max(peak, occ)
        # Shorts of batch 1 (indices 1-3) retire after ~3 steps; the
        # long pole keeps the batch open — nothing new may join it.
        if (not streams[0].finished
                and any(s.finished for s in streams[1:4])
                and any(not s.finished and s.tokens_so_far()
                        for s in streams[4:])):
            batch2_started_before_batch1_done = True
        assert stat_steps < 10000
    for (p, n), s in zip(reqs, streams):
        assert s.tokens_so_far() == m2.oracle(p, n)
    # A real static batcher runs FULL batches (4-wide here, not 1)...
    assert peak == 4, f"static batches formed at width {peak}, not 4"
    # ...and never refills a held batch mid-flight.
    assert not batch2_started_before_batch1_done
    # Same outputs, strictly worse step count than continuous.
    assert stat_steps > cont_steps


def test_preemption_requeues_and_recovers_exactly():
    """Cache pressure preempts the lowest-priority sequence —
    deterministically, without crashing the loop — and the preempted
    sequence still produces its exact oracle output after requeue +
    recompute."""
    m = TinyLM()
    # Tiny cache: 6 blocks of 4 = 24 tokens total. Two long sequences
    # (3 prompt + 18 new = 21 tokens each) cannot coexist.
    eng = InferenceEngine(m, EngineConfig(max_batch_size=4, block_size=4,
                                          num_blocks=6))
    hi = eng.submit([3, 5, 7], 18, priority=1)
    lo = eng.submit([2, 4, 6], 18, priority=0)
    _drive(eng)
    assert hi.tokens_so_far() == m.oracle([3, 5, 7], 18)
    assert lo.tokens_so_far() == m.oracle([2, 4, 6], 18)
    assert eng.preemptions > 0
    idx = eng.prefix_index
    assert (eng.cache.free_blocks()
            == eng.cache.num_blocks - idx.held_blocks())
    idx.release_all()
    assert eng.cache.free_blocks() == eng.cache.num_blocks


def test_preemption_victim_is_lowest_priority():
    m = TinyLM()
    eng = InferenceEngine(m, EngineConfig(max_batch_size=4, block_size=2,
                                          num_blocks=8))
    # Fill the cache with one high-priority long run + one low-priority.
    hi = eng.submit([3, 5], 10, priority=5)
    lo = eng.submit([2, 4], 10, priority=0)
    while eng.step():
        pass
    assert eng.preemptions > 0
    assert hi.finished and lo.finished
    assert hi.tokens_so_far() == m.oracle([3, 5], 10)
    assert lo.tokens_so_far() == m.oracle([2, 4], 10)


def test_submit_rejections_are_deterministic():
    eng = InferenceEngine(TinyLM(), EngineConfig(
        block_size=4, num_blocks=4, max_queue=2))
    with pytest.raises(CacheOverflowError):
        eng.submit([1] * 10, 20)           # can never fit: reject at door
    eng.submit([2, 2], 4)
    eng.submit([2, 3], 4)
    with pytest.raises(EngineOverloadedError):
        eng.submit([2, 4], 4)              # queue full: shed signal
    _drive(eng)


def test_cancellation_frees_blocks_and_finishes_stream():
    m = TinyLM()
    eng = InferenceEngine(m, EngineConfig(block_size=4, num_blocks=16))
    s = eng.submit([5, 5], 50)
    for _ in range(5):
        eng.step()
    assert not s.finished
    s.cancel()
    eng.step()
    assert s.finished
    assert eng.cache.free_blocks() == eng.cache.num_blocks
    # Cancelled-while-waiting also retires cleanly.
    eng2 = InferenceEngine(TinyLM(), EngineConfig(
        max_batch_size=1, block_size=4, num_blocks=16))
    a = eng2.submit([2], 3)
    b = eng2.submit([3], 3)
    b.cancel()
    _drive(eng2)
    assert a.finished and b.finished
    assert b.tokens_so_far() == []


def test_model_failure_poisons_batch_not_loop():
    class Exploding(TinyLM):
        def __init__(self):
            super().__init__()
            self.boom = False

        def decode(self, kvs, last_tokens, positions):
            if self.boom:
                self.boom = False
                raise RuntimeError("kaboom")
            return super().decode(kvs, last_tokens, positions)

    m = Exploding()
    eng = InferenceEngine(m, EngineConfig(block_size=4, num_blocks=32))
    s1 = eng.submit([5, 5], 10)
    eng.step()            # prefill + first decode ok
    m.boom = True
    eng.step()            # decode explodes: batch poisoned, loop alive
    assert s1.finished
    with pytest.raises(RuntimeError, match="kaboom"):
        list(s1)
    # The loop survives: new work runs to completion.
    s2 = eng.submit([4, 4], 5)
    _drive(eng)
    assert s2.tokens_so_far() == TinyLM().oracle([4, 4], 5)
    assert eng.cache.free_blocks() == eng.cache.num_blocks


# ---------------------------------------------------------------------------
# token streaming
# ---------------------------------------------------------------------------
def test_stream_sync_iteration_is_incremental():
    """First token is consumable while the engine is still decoding —
    TTFT decouples from completion (threaded engine, slowed model)."""
    m = TinyLM(step_delay_s=0.02)
    eng = InferenceEngine(m, EngineConfig(block_size=4, num_blocks=32))
    eng.start()
    try:
        s = eng.submit([6, 2], 10)
        it = iter(s)
        first = next(it)
        assert not s.finished, \
            "first token must arrive before generation completes"
        rest = list(it)
        assert [first] + rest == m.oracle([6, 2], 10)
    finally:
        eng.stop()


def test_stream_async_iteration():
    import asyncio

    m = TinyLM()
    eng = InferenceEngine(m, EngineConfig(block_size=4, num_blocks=32))
    eng.start()

    async def consume():
        s = eng.submit([8, 3], 8)
        return [tok async for tok in s]

    try:
        out = asyncio.run(consume())
        assert out == m.oracle([8, 3], 8)
    finally:
        eng.stop()


def test_stop_unblocks_consumers():
    from ray_tpu.serve.engine import EngineStoppedError

    eng = InferenceEngine(TinyLM(step_delay_s=0.05),
                          EngineConfig(block_size=4, num_blocks=32))
    eng.start()
    s = eng.submit([5], 50)
    got = []
    err = []

    def consume():
        try:
            for tok in s:
                got.append(tok)
        except EngineStoppedError as e:
            err.append(e)

    t = threading.Thread(target=consume)
    t.start()
    time.sleep(0.12)
    eng.stop()
    t.join(timeout=5)
    assert not t.is_alive()
    assert err, "consumer must see EngineStoppedError, not hang"


def test_engine_stats_and_ttft():
    eng = InferenceEngine(TinyLM(), EngineConfig(block_size=4,
                                                 num_blocks=32))
    eng.submit([5, 2], 4)
    _drive(eng)
    st = eng.stats()
    assert st["finished"] == 1
    assert st["tokens_generated"] == 4
    assert st["ttft_p50_ms"] is not None
    assert st["cache"]["utilization"] == 0.0
    assert st["prefill_s"] > 0 and st["decode_s"] > 0


# ---------------------------------------------------------------------------
# deferred delivery: a step's tokens go out beside the next step
# ---------------------------------------------------------------------------
class _Shadowed(TinyLM):
    """Logs what the streams have seen (tokens so far, finished) at each
    point of a call: a decode step's entry (`dispatch`), the end of its
    `meanwhile` (`delivered`), its arithmetic (`compute`: what a device
    model waits for), and a prefill's call (`prefill`)."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        self.log = []
        self.streams = []
        self.boom = False

    def _mark(self, what):
        self.log.append((what, [(len(s.tokens_so_far()), s.finished)
                                for s in self.streams]))

    def decode_paged(self, *args, meanwhile=None):
        self._mark("dispatch")

        def delivering():
            meanwhile()
            self._mark("delivered")

        return super().decode_paged(
            *args, meanwhile=None if meanwhile is None else delivering)

    def decode(self, kvs, last_tokens, positions):
        self._mark("compute")
        if self.boom:
            raise RuntimeError("kaboom")
        return super().decode(kvs, last_tokens, positions)

    def prefill(self, tokens, prefix_kv=None):
        self._mark("prefill")
        return super().prefill(tokens, prefix_kv)


def _shadowed(requests, **config):
    model = _Shadowed()
    eng = InferenceEngine(model, EngineConfig(block_size=4, num_blocks=64,
                                              **config))
    model.streams = [eng.submit(p, n) for p, n in requests]
    return model, eng, model.streams


def test_a_steps_tokens_reach_their_streams_inside_the_next_steps_meanwhile():
    model, eng, (a, b) = _shadowed([([5, 9, 3], 6), ([2, 2], 6)])
    assert eng.step()               # two prefills, the first decode step
    # Each stream has its prefill's token, at once; the step's are held.
    assert [len(s.tokens_so_far()) for s in (a, b)] == [1, 1]
    assert eng.stats()["tokens_generated"] == 4
    assert eng.stats()["tokens_delivered_overlapped"] == 0
    model.log.clear()
    assert eng.step()
    # dispatch -> the step before's tokens -> the wait for this step's.
    assert model.log == [("dispatch", [(1, False), (1, False)]),
                         ("delivered", [(2, False), (2, False)]),
                         ("compute", [(2, False), (2, False)])]
    assert eng.stats()["tokens_delivered_overlapped"] == 2
    _drive(eng)
    for stream, (p, n) in zip((a, b), [([5, 9, 3], 6), ([2, 2], 6)]):
        assert stream.tokens_so_far() == model.oracle(p, n)
        assert stream.finished
    stats = eng.stats()
    # 5 decode steps of 2 rows; all but the last step's went out in a
    # step's shadow, and the last step had none to follow it.
    assert stats["tokens_generated"] - stats["prefills"] == 10
    assert stats["tokens_delivered_overlapped"] == 8
    assert [what for what, _ in model.log].count("delivered") == \
        stats["paged_steps"] - 1 == 4


def test_the_last_token_and_the_finish_arrive_without_a_further_step():
    model, eng, (only,) = _shadowed([([7, 7], 3)])
    assert eng.step() and eng.step()                # tokens 2 and 3
    assert only.finished
    assert only.tokens_so_far() == model.oracle([7, 7], 3)
    assert eng.stats()["finished"] == 1
    assert model.decode_calls == 2
    assert eng.drain(timeout_s=0.1)
    assert eng.step() is False and model.decode_calls == 2


def test_a_sequence_that_ends_beside_others_ends_in_the_next_steps_shadow():
    model, eng, (short, long) = _shadowed([([4], 2), ([3, 3, 3], 5)])
    eng.step()                      # prefills; the step ends `short`
    assert eng.batch_occupancy() == 1 and not short.finished
    assert eng.cache.block_table("seq-0") == []     # its blocks are free
    model.log.clear()
    eng.step()
    assert model.log[1] == ("delivered", [(2, True), (2, False)])
    assert eng.stats()["finished"] == 1


def test_pending_tokens_are_flushed_before_a_prefills_call():
    model, eng, (first,) = _shadowed([([5, 9, 3], 8)], max_batch_size=2)
    eng.step()
    eng.step()
    assert len(first.tokens_so_far()) == 2          # one token is pending
    late = eng.submit([2, 2], 4)
    model.streams.append(late)
    model.log.clear()
    overlapped = eng.stats()["tokens_delivered_overlapped"]
    eng.step()
    # The pending token went out before the prefill's call began, not
    # behind it; the decode step that followed had nothing left to hand
    # over.
    assert model.log == [("prefill", [(3, False), (0, False)]),
                         ("dispatch", [(3, False), (1, False)]),
                         ("delivered", [(3, False), (1, False)]),
                         ("compute", [(3, False), (1, False)])]
    assert eng.stats()["tokens_delivered_overlapped"] == overlapped
    _drive(eng)
    assert first.tokens_so_far() == model.oracle([5, 9, 3], 8)
    assert late.tokens_so_far() == model.oracle([2, 2], 4)


@pytest.mark.parametrize("ending", ["cancel", "decode_raises", "stop"])
def test_a_pending_token_precedes_whatever_ends_its_stream(ending):
    from ray_tpu.serve.engine import EngineStoppedError

    model, eng, (stream,) = _shadowed([([5, 5], 50)])
    eng.step()
    eng.step()
    want = model.oracle([5, 5], 3)
    assert stream.tokens_so_far() == want[:2] and not stream.finished
    if ending == "cancel":
        stream.cancel()
        eng.step()
        error = None
    elif ending == "decode_raises":
        model.boom = True           # after the dispatch and the meanwhile
        eng.step()
        error = RuntimeError
        want = model.oracle([5, 5], 3)
    else:
        eng.stop()
        error = EngineStoppedError
    assert stream.finished
    assert stream.tokens_so_far() == want
    got = []
    if error is None:
        got = list(stream)
    else:
        with pytest.raises(error):
            for tok in stream:
                got.append(tok)
    assert got == want              # every token, then the end
    assert eng.cache.free_blocks() == eng.cache.num_blocks - (
        eng.prefix_index.held_blocks())


def test_a_step_that_fails_before_its_meanwhile_delivers_first():
    """A failure before the model reached its `meanwhile` (here: in the
    call itself) leaves the step before's token pending; it still comes
    before the error."""
    model, eng, (stream,) = _shadowed([([5, 5], 50)])
    eng.step()
    eng.step()

    def broken(*args, **kwargs):
        raise RuntimeError("no step")

    model.decode_paged = broken
    eng.step()
    got = []
    with pytest.raises(RuntimeError, match="no step"):
        for tok in stream:
            got.append(tok)
    assert got == model.oracle([5, 5], 3)


def test_a_preempted_sequences_pending_token_precedes_its_next_first_token():
    """Preemption requeues a sequence whose last token may still be
    pending; the prefill that readmits it delivers that token before its
    own."""
    m = TinyLM()
    eng = InferenceEngine(m, EngineConfig(max_batch_size=4, block_size=4,
                                          num_blocks=6))
    hi = eng.submit([3, 5, 7], 18, priority=1)
    lo = eng.submit([2, 4, 6], 18, priority=0)
    seen = []
    while eng.step():
        seen.append((hi.tokens_so_far(), lo.tokens_so_far()))
    assert eng.preemptions > 0
    for (h0, l0), (h1, l1) in zip(seen, seen[1:]):
        assert h1[:len(h0)] == h0 and l1[:len(l0)] == l0
    assert hi.tokens_so_far() == m.oracle([3, 5, 7], 18)
    assert lo.tokens_so_far() == m.oracle([2, 4, 6], 18)


def test_the_hosted_loop_delivers_every_token_once_and_in_order():
    m = TinyLM(step_delay_s=0.001)
    eng = InferenceEngine(m, EngineConfig(max_batch_size=4, block_size=4,
                                          num_blocks=64))
    eng.start()
    try:
        reqs = [([3 + i, 5], 12 + i) for i in range(8)]
        got = {}

        def consume(i, stream):
            got[i] = list(stream)

        threads = [threading.Thread(target=consume,
                                    args=(i, eng.submit(p, n)))
                   for i, (p, n) in enumerate(reqs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
        for i, (p, n) in enumerate(reqs):
            assert got[i] == m.oracle(p, n)
        assert eng.drain(timeout_s=5)
        stats = eng.stats()
        assert stats["finished"] == 8
        assert 0 < stats["tokens_delivered_overlapped"] <= \
            stats["tokens_generated"] - stats["prefills"]
    finally:
        eng.stop()


def test_shipped_prefixes_arrive_while_steps_deliver_in_their_shadow():
    """A step's `meanwhile` runs under the cache's lock, and the gauges
    it updates read the prefix index; `import_prefix` on another thread
    holds the index's lock while it takes the cache's. Neither may wait
    for the other: both finish, in bounded time."""
    import sys

    m = TinyLM(step_delay_s=0.0005)
    eng = InferenceEngine(m, EngineConfig(max_batch_size=4, block_size=4,
                                          num_blocks=512))
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    eng.start()
    stuck = True        # a deadlocked loop holds the lock `stop` needs
    try:
        reqs = [([3 + i, 5, 7], 150) for i in range(4)]
        streams = [eng.submit(p, n) for p, n in reqs]
        shipped = []

        def ship():
            rng = np.random.default_rng(0)
            for _ in range(600):
                chunks = [tuple(int(t) for t in rng.integers(2, 30, 4))
                          for _ in range(2)]
                shipped.append(eng.import_prefix(
                    chunks, [np.ones((4, 1), np.float32)] * 2))

        shipper = threading.Thread(target=ship, daemon=True)
        shipper.start()
        shipper.join(timeout=30)
        stuck = shipper.is_alive()
        assert not stuck, "import_prefix and a decode step deadlocked"
        assert len(shipped) == 600
        assert eng.drain(timeout_s=30)
        for (p, n), stream in zip(reqs, streams):
            assert stream.tokens_so_far() == m.oracle(p, n)
    finally:
        sys.setswitchinterval(interval)
        if not stuck:
            eng.stop(timeout_s=1.0)


# ---------------------------------------------------------------------------
# transformer decode shim (real-model path, still CPU-fast)
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


def test_transformer_prefill_matches_training_forward(tiny_transformer):
    import jax.numpy as jnp

    from ray_tpu.models.transformer import forward
    from ray_tpu.serve.engine import TransformerEngineModel

    params, cfg = tiny_transformer
    model = TransformerEngineModel(params, cfg)
    prompt = [3, 17, 42, 9, 21]
    logits, kv = model.prefill(prompt)
    assert len(kv) == 5
    assert np.asarray(kv).shape == (5, cfg.n_layers, 2, cfg.n_heads,
                                    cfg.head_dim)
    full, _ = forward(params, jnp.asarray([prompt], jnp.int32), cfg)
    np.testing.assert_allclose(logits, np.asarray(full)[0, -1],
                               atol=1e-4)


def test_transformer_incremental_decode_matches_full_recompute(
        tiny_transformer):
    """KV-cache decoding through the engine == greedy full-forward
    recompute, token for token — the cache-correctness acceptance for
    the real-model path."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import forward
    from ray_tpu.serve.engine import (EngineConfig, InferenceEngine,
                                      TransformerEngineModel)

    params, cfg = tiny_transformer
    model = TransformerEngineModel(params, cfg, max_batch_size=4)
    eng = InferenceEngine(model, EngineConfig(
        max_batch_size=2, block_size=8, num_blocks=16))
    prompts = [[3, 17, 42, 9], [7, 7]]
    streams = [eng.submit(p, 5) for p in prompts]
    while eng.step():
        pass

    for p, s in zip(prompts, streams):
        seq, oracle = list(p), []
        for _ in range(5):
            lg, _ = forward(params, jnp.asarray([seq], jnp.int32), cfg)
            t = int(np.argmax(np.asarray(lg)[0, -1]))
            oracle.append(t)
            if t == model.eos_token:
                break
            seq.append(t)
        assert s.tokens_so_far() == oracle


def test_transformer_prefill_from_offset_matches_full(tiny_transformer):
    """Prefill-from-offset (the prefix's KV written into a pool, the
    tail through `prefill_paged`, which gathers the prefix inside its
    jit) equals the full prefill's logits and tail KV — the compute half
    of prefix sharing on the real-model path."""
    from ray_tpu.serve.engine import (KVCacheManager,
                                      TransformerEngineModel)

    params, cfg = tiny_transformer
    model = TransformerEngineModel(params, cfg)
    prompt = [3, 17, 42, 9, 21, 5, 11, 2, 33, 40]
    full_logits, full_kv = model.prefill(prompt)
    full_kv = np.asarray(full_kv)
    for p in (4, 8, 9):
        cache = KVCacheManager(8, 4, kv_shape=model.kv_token_shape,
                               array_ns=model.kv_pool_ns)
        assert cache.allocate("s", len(prompt), writable_from=0)
        cache.write_range("s", 0, full_kv[:p])
        table = cache.block_table("s")
        logits, tail_kv = cache.with_pool(
            lambda pool: model.prefill_paged(prompt, pool, table, p, 4))
        assert len(tail_kv) == len(prompt) - p
        np.testing.assert_allclose(logits, full_logits, atol=1e-4)
        np.testing.assert_allclose(tail_kv, full_kv[p:], atol=1e-4)


def test_transformer_engine_sharing_matches_no_sharing(tiny_transformer):
    """Engine generation with prefix sharing (adoption + cached
    prefill + COW) is token-for-token equal to the no-sharing engine
    over the real transformer."""
    from ray_tpu.serve.engine import (EngineConfig, InferenceEngine,
                                      TransformerEngineModel)

    params, cfg = tiny_transformer
    base = [3, 17, 42, 9, 21, 5, 11, 2]        # seals one 8-block
    reqs = [(base + [33], 4), (base + [40], 4), (base + [33], 4)]
    outs = []
    for sharing in (False, True):
        model = TransformerEngineModel(params, cfg, max_batch_size=4)
        eng = InferenceEngine(model, EngineConfig(
            max_batch_size=4, block_size=8, num_blocks=16,
            prefix_sharing=sharing))
        streams = [eng.submit(p, n) for p, n in reqs]
        while eng.step():
            pass
        outs.append([s.tokens_so_far() for s in streams])
        if sharing:
            assert eng.prefix_hit_tokens >= 16   # two adopters x 8
    assert outs[0] == outs[1]


def test_a_fully_cached_prompt_compiles_no_program_of_its_own(
        tiny_transformer):
    """A prompt that ends on a block's edge, sent again once it is
    cached: its first token is one read-only decode step at position
    ``n - 1``, whose live pages are a column fewer than its next step's.
    The scheduler hands the table in at the next step's width and the
    model's bucket holds the widest table, so the step runs in the
    program the first request's decode steps compiled: same tokens,
    no compile (a closed loop that wraps around its requests inside a
    measured window met one: PERF.md, PR 50)."""
    from ray_tpu.serve.engine import (EngineConfig, InferenceEngine,
                                      TransformerEngineModel)

    params, cfg = tiny_transformer
    model = TransformerEngineModel(params, cfg, max_batch_size=2)
    model.eos_token = None
    eng = InferenceEngine(model, EngineConfig(
        max_batch_size=2, block_size=4, num_blocks=32))
    prompt = [3, 17, 42, 9, 21, 5, 11, 2]      # two whole blocks
    first = eng.submit(prompt, 3)
    while eng.step():
        pass
    compiled, hits = model.jit_compiles, eng.prefix_hit_tokens
    again = eng.submit(prompt, 3)
    while eng.step():
        pass
    assert eng.prefix_hit_tokens - hits == len(prompt)
    assert again.tokens_so_far() == first.tokens_so_far()
    assert model.jit_compiles == compiled


def test_prefill_flight_event_carries_prefix_hit():
    """Engine prefill events in the flight ring report the shared-
    prefill savings (`prefix_hit`) so /api/timeline shows them."""
    from ray_tpu.core import flight

    prev = flight.enabled
    flight.enable()
    try:
        flight.configure(256)
        m = TinyLM()
        eng = InferenceEngine(m, EngineConfig(block_size=4,
                                              num_blocks=32))
        prompt = [3, 5, 7, 9, 2, 4, 6, 8]
        eng.submit(prompt, 3)
        _drive(eng)
        eng.submit(prompt, 3)                  # full prefix hit
        _drive(eng)
        args = [ev[5] for ev in flight.snapshot(categories={"engine"})
                if ev[3] == "prefill"]
        assert "tokens=8 prefix_hit=0" in args
        assert "tokens=8 prefix_hit=8" in args
    finally:
        if not prev:
            flight.disable()


def test_transformer_shape_buckets_are_bounded(tiny_transformer):
    from ray_tpu.serve.engine import (EngineConfig, InferenceEngine,
                                      TransformerEngineModel)

    params, cfg = tiny_transformer
    model = TransformerEngineModel(params, cfg, max_batch_size=4)
    eng = InferenceEngine(model, EngineConfig(
        max_batch_size=4, block_size=8, num_blocks=32))
    # Varied prompt lengths and arrival patterns...
    for p, n in (([3], 3), ([4, 5], 4), ([6, 7, 8], 5),
                 ([9] * 5, 6), ([10] * 7, 3)):
        eng.submit(p, n)
    while eng.step():
        pass
    # ...compile only power-of-two buckets, not one shape per mix.
    for b, nb, block_size in model._decode_paged_jit:
        assert b & (b - 1) == 0 and nb & (nb - 1) == 0 and block_size == 8
    assert len(model._decode_paged_jit) <= 6
    assert len(model._prefill_jit) <= 3


# ---------------------------------------------------------------------------
# the prompt KV's hand-over: prefill's device rows -> the pool's scatter
# ---------------------------------------------------------------------------
def _pool_bytes(cache):
    return cache.with_pool(lambda pool: np.array(pool))


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(2, 64, n).tolist()


@pytest.mark.parametrize("n", [5, 8, 33, 48, 63, 64])
def test_prompt_kv_reaches_a_device_pool_without_the_host(
        tiny_transformer, n):
    """`model.prefill` then `cache.write_range`, as the scheduler's
    no-hit path makes them: the padded device rows land exactly where
    the host-payload path (`np.asarray` of the same KV) puts them, the
    bucket's padding rows drop, and the counters say which way it went."""
    from ray_tpu.serve.engine import (EngineConfig, InferenceEngine,
                                      KVCacheManager,
                                      TransformerEngineModel)

    params, cfg = tiny_transformer
    model = TransformerEngineModel(params, cfg)
    eng = InferenceEngine(model, EngineConfig(
        block_size=16, num_blocks=12))
    cache = eng.cache
    by_host = KVCacheManager(12, 16, kv_shape=model.kv_token_shape,
                             array_ns=model.kv_pool_ns)
    # A neighbour whose last block the sequence under test follows.
    prompts = {"neighbour": _tokens(19, 1), "s": _tokens(n, n)}
    logits = {}
    for sid, tokens in prompts.items():
        for c in (cache, by_host):
            assert c.allocate(sid, len(tokens), writable_from=0)
        logits[sid], kv = model.prefill(tokens)
        assert len(kv) == len(tokens)
        assert not isinstance(kv, np.ndarray)
        before = np.asarray(cache.gather("neighbour"))
        cache.write_range(sid, 0, kv)
        by_host.write_range(sid, 0, np.asarray(kv))
    np.testing.assert_array_equal(
        np.asarray(cache.gather("s")), np.asarray(by_host.gather("s")))
    np.testing.assert_array_equal(np.asarray(cache.gather("s")),
                                  np.asarray(kv))
    # Nothing else in the pool moved: the neighbour's rows, and every
    # block the two pools did not write (the padding rows dropped).
    np.testing.assert_array_equal(np.asarray(cache.gather("neighbour")),
                                  before)
    np.testing.assert_array_equal(_pool_bytes(cache), _pool_bytes(by_host))
    s = eng.stats()
    assert s["prefill_kv_host_writes"] == 0
    assert s["prefill_kv_device_writes"] == 2 == model.prefill_calls
    assert (by_host.range_writes_device, by_host.range_writes_host) == (0, 2)

    # The first generated token, read through each pool by the paged
    # step, and by the full forward.
    import jax.numpy as jnp

    from ray_tpu.models.transformer import forward

    tokens = prompts["s"]
    tok = int(np.argmax(logits["s"]))
    step = []
    for c in (cache, by_host):
        assert c.allocate("s", n + 1, writable_from=n)
        table = c.block_table("s")
        step.append(np.asarray(c.paged_step(
            [("s", n)], lambda pool, blocks, offs: model.decode_paged(
                pool, [table], [tok], [n], blocks, offs, 16)))[0])
    np.testing.assert_array_equal(step[0], step[1])
    full, _ = forward(params, jnp.asarray([tokens + [tok]], jnp.int32), cfg)
    np.testing.assert_allclose(logits["s"], np.asarray(full)[0, n - 1],
                               atol=1e-4)
    np.testing.assert_allclose(step[0], np.asarray(full)[0, n], atol=1e-4)


@pytest.mark.parametrize("shared, first, others", [
    (0, 33, (40, 48, 63)), (32, 41, (44, 47, 48))],
    ids=["no_hit", "prefix_hit"])
def test_prefill_compiles_per_bucket_not_per_prompt_length(
        tiny_transformer, shared, first, others):
    """After one prefill in a bucket, other prompt lengths of the same
    bucket build no program: not the model's jits, and no slice, pad or
    scatter compiled for the exact length. `shared` tokens of prefix
    hit take the paged prefill-from-offset, by the same hand-over (its
    bucket is the tail's)."""
    from benchmarks.harness.serve_cell import CompileCounter
    from ray_tpu.serve.engine import (EngineConfig, InferenceEngine,
                                      TransformerEngineModel)

    params, cfg = tiny_transformer
    model = TransformerEngineModel(params, cfg)
    eng = InferenceEngine(model, EngineConfig(
        block_size=16, num_blocks=32))
    head = _tokens(shared, 7)
    # JAX's own compile event, as the benchmark's cells count it.
    events = CompileCounter()

    def prefill(n, seed):
        stream = eng.submit(head + _tokens(n - shared, seed), 1)
        while eng.step():
            pass
        assert len(list(stream)) == 1

    if shared:
        prefill(shared + 1, 99)     # seals the head's two blocks
    prefill(first, 0)
    assert events.count > 0
    warm = (events.count, model.jit_compiles)
    for n in others:
        prefill(n, n)
    assert (events.count, model.jit_compiles) == warm
    s = eng.stats()
    assert s["prefix_hit_tokens"] == (4 * shared if shared else 0)
    assert s["prefill_kv_host_writes"] == 0
    assert s["prefill_kv_device_writes"] == s["prefills"] == \
        (5 if shared else 4)


def test_host_pool_takes_a_device_payload_through_one_host_copy(
        tiny_transformer):
    """A numpy pool (the manager's default, `TinyLM`'s) handed a
    `PromptKV`: one host copy of the bucket, cut to `len()` rows, and
    counted as a host write."""
    from ray_tpu.serve.engine import (KVCacheManager,
                                      TransformerEngineModel)

    params, cfg = tiny_transformer
    model = TransformerEngineModel(params, cfg)
    cache = KVCacheManager(8, 16, kv_shape=model.kv_token_shape)
    assert cache.pool_residency == "host"
    tokens = _tokens(21, 3)
    assert cache.allocate("s", 21, writable_from=0)
    _, kv = model.prefill(tokens)
    assert hasattr(kv.padded, "block_until_ready") and len(kv) == 21
    cache.write_range("s", 0, kv)
    rows = cache.gather("s")
    assert rows.shape == (21,) + model.kv_token_shape
    np.testing.assert_array_equal(rows, np.asarray(kv.padded)[:21])
    assert (cache.range_writes_device, cache.range_writes_host) == (0, 1)


# ---------------------------------------------------------------------------
# a paged decode step's host boundary: one packed upload in, sampled ids
# out, the logits left on the device
# ---------------------------------------------------------------------------
def _paged_transformer_engine(params, cfg, **config):
    from ray_tpu.serve.engine import TransformerEngineModel

    config.setdefault("block_size", 16)
    model = TransformerEngineModel(params, cfg)
    model.eos_token = None      # random weights: no token ends a stream
    return model, InferenceEngine(model, EngineConfig(
        num_blocks=64, **config))


def _wrap_decode_paged(model, after):
    """Pass every paged step's result through `after`."""
    inner = model.decode_paged

    def decode_paged(*args, **kwargs):
        step, pool = inner(*args, **kwargs)
        return after(step), pool

    model.decode_paged = decode_paged


@pytest.mark.parametrize("b, block_size, prompt_len, new_tokens", [
    (1, 16, 5, 4), (3, 16, 6, 4), (8, 16, 5, 4), (2, 4, 3, 7)],
    ids=["b1", "b3", "b8", "row_crosses_blocks"])
def test_sampled_ids_are_the_argmax_of_the_steps_own_logits(
        tiny_transformer, b, block_size, prompt_len, new_tokens):
    """The ids the packed program samples equal `np.argmax` of the
    logits fetched from the same step, and the token streams through the
    ids equal those through the scheduler's logits fallback (the same
    model, handing plain host logits to `engine.sample`)."""
    from ray_tpu.serve.engine.model import DecodeStep

    params, cfg = tiny_transformer
    prompts = [_tokens(prompt_len + i % 2, 100 + i) for i in range(b)]
    streams, seen = {}, []

    def check(step):
        assert isinstance(step, DecodeStep)
        seen.append(len(step))
        np.testing.assert_array_equal(
            step.ids, np.argmax(np.asarray(step), axis=-1))
        return step

    for way, after in (("ids", check), ("logits", np.asarray)):
        model, eng = _paged_transformer_engine(
            params, cfg, block_size=block_size, max_batch_size=b)
        _wrap_decode_paged(model, after)
        handles = [eng.submit(p, new_tokens) for p in prompts]
        _drive(eng)
        streams[way] = [h.tokens_so_far() for h in handles]
        assert eng.stats()["paged_steps"] == new_tokens - 1
    assert streams["ids"] == streams["logits"]
    assert all(len(s) == new_tokens for s in streams["ids"])
    assert seen == [b] * (new_tokens - 1)


def test_a_tie_in_the_logits_goes_to_the_lowest_index(tiny_transformer):
    """Every row of the embedding the same: every logit of a row ties,
    and the jit's argmax, like `np.argmax`, takes index 0."""
    import jax.numpy as jnp

    params, cfg = tiny_transformer
    tied = dict(params, embed=jnp.tile(params["embed"][3:4],
                                       (cfg.vocab_size, 1)))
    model, eng = _paged_transformer_engine(tied, cfg)
    step = eng.cache.mutate_pool(lambda pool: model.decode_paged(
        pool, [[0]] * 3, [5, 9, 2], [3, 7, 0], [], [], 16))
    logits = np.asarray(step)
    assert np.abs(logits).min() > 0
    np.testing.assert_array_equal(np.ptp(logits, axis=-1), 0.0)
    assert step.ids.tolist() == [0, 0, 0] == \
        np.argmax(logits, axis=-1).tolist()


@pytest.mark.parametrize("b", [1, 3, 8])
def test_a_step_uploads_one_array_and_brings_back_its_ids(
        tiny_transformer, b):
    """`decode_h2d_arrays` grows by 1 a step and `decode_d2h_bytes` by
    the ids (at the width of the model's largest batch bucket, whatever
    the step's rows: PR 60); `np.asarray(step)` has `b` rows, fetches the padded
    logits once and adds their bytes; an empty write list leaves the
    pool as it was."""
    from ray_tpu.serve.engine.model import _next_pow2

    params, cfg = tiny_transformer
    model, eng = _paged_transformer_engine(params, cfg)
    b_pad = _next_pow2(b)
    assert eng.cache.allocate("s", 20, writable_from=0)
    eng.cache.write_range("s", 0, model.prefill(_tokens(20, 4))[1])
    table = eng.cache.block_table("s")
    pool_before = _pool_bytes(eng.cache)
    before = eng.stats()
    step = eng.cache.mutate_pool(lambda pool: model.decode_paged(
        pool, [table] * b, [7] * b, [19] * b, [], [], 16))
    np.testing.assert_array_equal(_pool_bytes(eng.cache), pool_before)
    after = eng.stats()
    assert after["decode_h2d_arrays"] - before["decode_h2d_arrays"] == 1
    assert after["decode_d2h_bytes"] - before["decode_d2h_bytes"] \
        == 4 * model._ids_width(b_pad) == 4 * 8
    assert len(step) == b and step.ids.dtype == np.int32

    logits = np.asarray(step)
    assert logits.shape == (b, cfg.vocab_size)
    assert logits.dtype == np.float32
    fetched = eng.stats()["decode_d2h_bytes"] - after["decode_d2h_bytes"]
    assert fetched == 4 * b_pad * cfg.vocab_size
    assert np.asarray(step) is logits
    assert eng.stats()["decode_d2h_bytes"] \
        == after["decode_d2h_bytes"] + fetched
    # The same rows read the same cache: one row's logits, b times.
    np.testing.assert_array_equal(logits, np.tile(logits[:1], (b, 1)))


def test_the_upload_count_follows_the_calls_arguments(tiny_transformer):
    """`decode_h2d_arrays` counts what the jitted call is handed from the
    host: weights left on the host would go up with every step, and show."""
    import jax

    params, cfg = tiny_transformer
    model, eng = _paged_transformer_engine(params, cfg)
    assert eng.cache.allocate("s", 20, writable_from=0)
    eng.cache.write_range("s", 0, model.prefill(_tokens(20, 4))[1])
    table = eng.cache.block_table("s")

    def one_step():
        before = model.decode_h2d_arrays
        step = eng.cache.mutate_pool(lambda pool: model.decode_paged(
            pool, [table], [7], [19], [], [], 16))
        return model.decode_h2d_arrays - before, step.ids

    count, ids = one_step()
    assert count == 1
    model._params = jax.tree_util.tree_map(np.asarray, model._params)
    count_host, ids_host = one_step()
    assert count_host == 1 + len(jax.tree_util.tree_leaves(params))
    np.testing.assert_array_equal(ids_host, ids)


def test_a_served_stream_fetches_no_logits(tiny_transformer):
    """Through the engine, every paged step is one upload and 4 bytes a
    row of the model's largest batch bucket back; a fully cached prompt's first token is the one
    place the scheduler asks a step for its logits."""
    params, cfg = tiny_transformer
    model, eng = _paged_transformer_engine(params, cfg, max_batch_size=4)
    assert (eng.stats()["decode_h2d_arrays"],
            eng.stats()["decode_d2h_bytes"]) == (0, 0)
    prompt = _tokens(32, 8)          # two full blocks: fully cacheable
    handles = [eng.submit(_tokens(5, i), 6) for i in range(3)]
    handles.append(eng.submit(prompt, 6))
    _drive(eng)
    s = eng.stats()
    assert s["paged_steps"] == 5 == s["decode_h2d_arrays"]
    assert s["decode_d2h_bytes"] == 5 * 4 * model._ids_width(4) == 5 * 4 * 8
    again = eng.submit(prompt, 2)
    _drive(eng)
    assert again.tokens_so_far() == handles[-1].tokens_so_far()[:2]
    s2 = eng.stats()
    assert s2["prefix_hit_tokens"] == 32
    # The read-only step of the full hit (b_pad 1) and one decode step,
    # each its ids at the model's width; the first fetched its [1, V]
    # logits.
    assert s2["decode_h2d_arrays"] - s["decode_h2d_arrays"] == 2
    assert s2["decode_d2h_bytes"] - s["decode_d2h_bytes"] \
        == 2 * 4 * model._ids_width(1) + 4 * cfg.vocab_size


def test_a_model_that_returns_logits_is_sampled_on_the_host():
    """`TinyLM`'s step returns host logits: the engine emits the
    oracle's tokens and counts nothing across the boundary. Both
    counters are in `stats()` from construction."""
    model = TinyLM(vocab_size=32)
    eng = InferenceEngine(model, EngineConfig(
        block_size=4, num_blocks=64, max_batch_size=3))
    s = eng.stats()
    assert (s["decode_h2d_arrays"], s["decode_d2h_bytes"]) == (0, 0)
    prompts = [[5, 9, 3], [7, 2, 11, 4, 6], [12]]
    handles = [eng.submit(p, 9) for p in prompts]
    _drive(eng)
    for p, h in zip(prompts, handles):
        assert h.tokens_so_far() == model.oracle(p, 9)
    s = eng.stats()
    assert s["paged_steps"] == 8
    assert (s["decode_h2d_arrays"], s["decode_d2h_bytes"]) == (0, 0)


# ---------------------------------------------------------------------------
# chunked prefill: a long prompt a chunk an iteration, a decode step between
# ---------------------------------------------------------------------------
class _Rows:
    """A prefill's payload a layer group, on the host."""

    def __init__(self, rows, groups=None):
        self.rows, self.groups = rows, groups or {}

    def __len__(self):
        return len(self.rows)

    def __array__(self, dtype=None, copy=None):
        return self.rows


class _ChunkLM(TinyLM):
    """`TinyLM` over a global and a window layer group (the token's value
    in both) that offers `prefill_chunk` and records its calls. A chunk
    computes from what the global pool holds of the positions before it,
    and finds the window group's rows of the window before it through
    the compact table, so a chunk the scheduler stored wrongly, or a
    block given back too early, changes a token or fails the call."""

    kv_groups = {"window": {"kv_shape": (1,), "window": 6}}
    prefill_chunk_tokens = 4

    def __init__(self):
        super().__init__()
        self.calls, self.seen, self.fail_at = [], [], None
        self.streams = []

    def _both(self, kv):
        return _Rows(kv, {"window": _Rows(kv.copy())})

    def prefill(self, tokens, prefix_kv=None):
        self.calls.append(("prefill", len(tokens)))
        logits, kv = super().prefill(tokens)
        return logits, self._both(kv)

    def prefill_chunk(self, tokens, pools, tables, start, block_size, *,
                      meanwhile=None):
        self.calls.append(("chunk", start))
        if self.fail_at == start:
            raise RuntimeError("kaboom")
        before = [len(s.tokens_so_far()) for s in self.streams]
        if meanwhile is not None:
            meanwhile()
        self.seen.append(
            (before, [len(s.tokens_so_far()) for s in self.streams]))
        end = min(len(tokens), start + self.prefill_chunk_tokens)
        _, table = tables["global"]
        cached = self._pool_gather(pools["global"], table, start, block_size)
        base, near = tables["window"]
        window = self.kv_groups["window"]["window"]
        for pos in range(max(0, start - window + 1), start):
            held = pools["window"][near[pos // block_size - base],
                                   pos % block_size, 0]
            assert held == tokens[pos], (pos, held)
        self.prefill_calls += 1
        self.prefill_tokens += end - start
        logits = None
        if end == len(tokens):
            nxt = self._next(float(cached.sum()) + sum(tokens[start:-1]),
                             tokens[-1], len(tokens) - 1)
            logits = np.full((self.vocab_size,), -1e30, np.float32)
            logits[nxt] = 0.0
        return logits, self._both(
            np.asarray(tokens[start:end], np.float32)[:, None])

    def decode_paged(self, pools, block_tables, last_tokens, positions,
                     write_blocks, write_offs, block_size, *,
                     meanwhile=None):
        self.calls.append(("decode", len(last_tokens)))
        logits, pools["global"] = super().decode_paged(
            pools["global"], [t["global"][1] for t in block_tables],
            last_tokens, positions, write_blocks["global"],
            write_offs["global"], block_size, meanwhile=meanwhile)
        for tok, block, off in zip(last_tokens, write_blocks["window"],
                                   write_offs["window"]):
            pools["window"][block, off] = tok
        return logits, pools


LONG = [5, 9, 3, 7, 2, 11, 4, 6, 12, 8, 10, 3, 5, 9]    # 14: 4 + 4 + 4 + 2


def _chunk_engine(model=None, **config):
    model = model or _ChunkLM()
    config = {"block_size": 4, "num_blocks": 64,
              "group_blocks": {"window": 16}, "max_batch_size": 4, **config}
    return model, InferenceEngine(model, EngineConfig(**config))


def _free(eng):
    return (eng.cache.free_blocks(), eng.cache.group("window").free_blocks())


def test_every_running_row_gets_one_token_between_two_chunks():
    model, eng = _chunk_engine()
    rows = [eng.submit([5, 9, 3], 12), eng.submit([2, 2], 12)]
    assert eng.step()                       # two whole prefills and a step
    model.calls.clear()
    late = eng.submit(LONG, 5)
    running = list(eng._running)
    lengths = []
    for _ in range(4):
        assert eng.step()
        lengths.append([len(s.all_tokens) for s in running])
    # An iteration: the prompt's next chunk, then the batch's step; the
    # prompt joins it behind its last chunk.
    assert model.calls == [("chunk", 0), ("decode", 2), ("chunk", 4),
                           ("decode", 2), ("chunk", 8), ("decode", 2),
                           ("chunk", 12), ("decode", 3)]
    assert [[b - a for a, b in zip(x, y)]
            for x, y in zip(lengths, lengths[1:])] == [[1, 1]] * 3
    assert eng._in_flight is None and len(eng._running) == 3
    _drive(eng)
    for stream, (p, n) in zip(rows + [late], [([5, 9, 3], 12), ([2, 2], 12),
                                              (LONG, 5)]):
        assert stream.tokens_so_far() == model.oracle(p, n)
        assert stream.finished
    stats = eng.stats()
    assert (stats["prefills"], stats["prefill_chunks"],
            stats["prefill_chunk_tokens"]) == (3, 4, 14)
    assert model.prefill_tokens == 5 + 14
    assert _free(eng) == (64, 16)


def test_with_nothing_running_the_chunks_follow_each_other_at_once():
    model, eng = _chunk_engine()
    stream = eng.submit(LONG, 3)
    assert eng.step()
    assert model.calls == [("chunk", 0), ("chunk", 4), ("chunk", 8),
                           ("chunk", 12), ("decode", 1)]
    _drive(eng)
    assert stream.tokens_so_far() == model.oracle(LONG, 3)


@pytest.mark.parametrize("policy", ["continuous", "static"])
def test_a_chunked_runs_tokens_are_the_whole_paths(policy):
    prompts = [(LONG, 6), ([7, 2, 11, 4], 9), (LONG[3:] + LONG, 4),
               ([3, 3, 4, 4, 5], 7)]
    got = {}
    for chunked in (True, False):
        model = _ChunkLM()
        if not chunked:
            model.prefill_chunk = None
        _, eng = _chunk_engine(model, policy=policy, max_batch_size=3)
        streams = [eng.submit(p, n) for p, n in prompts]
        _drive(eng)
        got[chunked] = [s.tokens_so_far() for s in streams]
        chunks = [c for c in model.calls if c[0] == "chunk"]
        # 14, 25 and 5 tokens in chunks of 4; 4 tokens whole.
        assert len(chunks) == (4 + 7 + 2 if chunked else 0)
        assert eng.stats()["prefills"] == 4
        assert _free(eng) == (64, 16)
    assert got[True] == got[False] == [model.oracle(p, n)
                                       for p, n in prompts]


def test_a_prompt_in_chunks_counts_once_and_its_clock_holds_every_chunk(
        recorder):
    model, eng = _chunk_engine()
    first = eng.submit([5, 9, 3], 20)
    eng.step()
    late = eng.submit(LONG, 2)
    _drive(eng)
    assert len(list(first)) == 20 and len(list(late)) == 2
    stats = eng.stats()
    assert (stats["prefills"], stats["prefill_chunks"]) == (2, 4)
    events = recorder.snapshot(categories={"engine"})
    waits = [e for e in events if e[3] == "queue_wait"]
    assert [e[5] for e in waits] == ["seq-0", "seq-1"]
    assert sum(e[4] for e in waits) * 1e-6 == pytest.approx(
        stats["queue_wait_s"], abs=1e-5)
    prefills = [e for e in events if e[3] == "prefill"]
    assert len(prefills) == 1 + 4
    assert sum(e[4] for e in prefills) * 1e-6 == pytest.approx(
        stats["prefill_s"], abs=1e-5 * 5)


def test_short_prompts_and_models_without_the_call_never_see_a_chunk():
    model, eng = _chunk_engine()
    exact = eng.submit(LONG[:4], 3)         # as long as a chunk: whole
    _drive(eng)
    assert exact.tokens_so_far() == model.oracle(LONG[:4], 3)
    assert model.calls[0] == ("prefill", 4)
    assert not [c for c in model.calls if c[0] == "chunk"]
    assert eng.stats()["prefill_chunks"] == 0
    plain = TinyLM()
    eng = InferenceEngine(plain, EngineConfig(block_size=4, num_blocks=64))
    assert eng._chunk is None
    stream = eng.submit(LONG, 3)
    _drive(eng)
    assert stream.tokens_so_far() == plain.oracle(LONG, 3)
    stats = eng.stats()
    assert (stats["prefill_chunks"], stats["prefill_chunk_tokens"]) == (0, 0)
    assert plain.prefill_calls == 1


def test_the_step_befores_tokens_go_out_from_a_chunks_meanwhile():
    model, eng = _chunk_engine()
    row = eng.submit([5, 9, 3], 12)
    model.streams = [row]
    eng.step()                              # its prefill and a step
    eng.step()
    assert eng.stats()["tokens_delivered_overlapped"] == 1
    eng.submit(LONG, 2)
    model.seen.clear()
    eng.step()
    # The chunk found the row's last step's token pending and handed it
    # over from its `meanwhile`; its own step then had none to hand.
    assert model.seen == [([2], [3])]
    assert eng.stats()["tokens_delivered_overlapped"] == 2
    eng.step()
    assert model.seen[1] == ([3], [4])
    assert eng.stats()["tokens_delivered_overlapped"] == 3
    _drive(eng)
    assert row.tokens_so_far() == model.oracle([5, 9, 3], 12)


def _mid_prompt():
    """An engine with one running row and a long prompt two chunks in."""
    model, eng = _chunk_engine()
    row = eng.submit([5, 9, 3], 30)
    eng.step()
    late = eng.submit(LONG, 5)
    eng.step()
    eng.step()
    assert eng._in_flight is not None and eng._in_flight.prefilled == 8
    assert _free(eng) == (64 - 2 - 2, 16 - 2 - 2)
    return model, eng, row, late


def test_a_cancellation_in_mid_prompt_frees_both_groups_and_ends_the_stream():
    model, eng, row, late = _mid_prompt()
    late.cancel()
    model.calls.clear()
    eng.step()
    assert eng._in_flight is None and late.finished
    assert late.tokens_so_far() == [] and list(late) == []
    assert ("chunk", 8) not in model.calls
    assert eng.stats()["finished"] == 1
    _drive(eng)
    assert row.tokens_so_far() == model.oracle([5, 9, 3], 30)
    assert eng.stats()["finished"] == 2 and _free(eng) == (64, 16)
    assert eng.cache.seq_len("seq-1") == 0


def test_stop_in_mid_prompt_frees_both_groups_and_ends_the_stream():
    from ray_tpu.serve.engine.scheduler import EngineStoppedError

    model, eng, row, late = _mid_prompt()
    eng.stop()
    assert eng._in_flight is None and _free(eng) == (64, 16)
    for stream in (row, late):
        assert stream.finished
        with pytest.raises(EngineStoppedError):
            list(stream)
    assert not eng.step()


def test_a_failing_chunk_ends_its_stream_alone_and_frees_both_groups():
    model, eng, row, late = _mid_prompt()
    model.fail_at = 8
    eng.step()
    assert eng._in_flight is None and late.finished
    with pytest.raises(RuntimeError, match="kaboom"):
        list(late)
    # The row's pending token went out before the stream was ended, and
    # the row runs on.
    _drive(eng)
    assert row.tokens_so_far() == model.oracle([5, 9, 3], 30)
    assert _free(eng) == (64, 16)
    assert eng.stats()["prefills"] == 1


def test_a_chunk_that_loses_its_blocks_requeues_its_prompt_at_the_head():
    model, eng, row, late = _mid_prompt()
    other = eng.submit([4, 4, 4], 2)        # waits behind the prompt
    allocate, lost = eng.cache.allocate, []

    def losing(seq_id, target, writable_from=None):
        if seq_id == "seq-1" and not lost:
            lost.append(target)
            return False
        return allocate(seq_id, target, writable_from=writable_from)

    eng.cache.allocate = losing
    eng.step()
    assert lost == [12] and eng._in_flight is None
    assert [s.seq_id for s in eng._waiting] == ["seq-1", "seq-2"]
    assert eng.cache.seq_len("seq-1") == 0
    assert _free(eng) == (64 - 2, 16 - 2)       # the row's alone
    model.calls.clear()
    _drive(eng)
    # It began again, before the request behind it.
    assert [c for c in model.calls if c[0] != "decode"][:5] == [
        ("chunk", 0), ("chunk", 4), ("chunk", 8), ("chunk", 12),
        ("prefill", 3)]
    assert late.tokens_so_far() == model.oracle(LONG, 5)
    assert other.tokens_so_far() == model.oracle([4, 4, 4], 2)
    assert eng.stats()["prefills"] == 3 and _free(eng) == (64, 16)


def test_the_victim_of_a_preemption_is_never_the_prompt_in_flight():
    model, eng, row, late = _mid_prompt()
    assert eng._pick_victim() is eng._running[0]
    assert eng._in_flight not in eng._running
    eng._preempt(eng._pick_victim())
    assert eng._pick_victim() is None and eng._in_flight is not None
    _drive(eng)
    assert late.tokens_so_far() == model.oracle(LONG, 5)
    assert row.tokens_so_far() == model.oracle([5, 9, 3], 30)


# ---------------------------------------------------------------------------
# the wait in `_pending` (stats: `pending_wait_s`, `pending_wait_tokens`)
# ---------------------------------------------------------------------------
def _pending_wait(engine):
    stats = engine.stats()
    return stats["pending_wait_s"], stats["pending_wait_tokens"]


def test_the_wait_in_pending_is_clocked_a_decode_token(recorder):
    eng = InferenceEngine(TinyLM(), EngineConfig(block_size=4, num_blocks=64,
                                                 max_batch_size=4))
    assert _pending_wait(eng) == (0.0, 0)        # there from construction
    streams = [eng.submit([3 + i, 5, 7], 9) for i in range(3)]
    last = (0.0, 0)
    while eng.step():
        now = _pending_wait(eng)
        assert now[0] >= last[0] and now[1] >= last[1]
        last = now
    assert all(len(s.tokens_so_far()) == 9 for s in streams)
    stats = eng.stats()
    seconds, tokens = _pending_wait(eng)
    # Every decode token waited there, a prefill's never does.
    assert tokens == stats["tokens_generated"] - stats["prefills"] == 24
    assert seconds > 0
    # Each waited at most from its step's end to the end of the run.
    assert seconds < tokens * stats["loop_s"]
    # No phase of the loop: the phases are the 19 they were.
    assert len([k for k in stats if k.startswith("phase.")]) == 19


def test_the_wait_in_pending_stands_still_with_the_recorder_off(recorder):
    recorder.disable()
    try:
        eng = InferenceEngine(TinyLM(), EngineConfig(block_size=4,
                                                     num_blocks=64))
        stream = eng.submit([3, 4, 5, 6], 8)
        _drive(eng)
        assert len(stream.tokens_so_far()) == 8
        assert _pending_wait(eng) == (0.0, 0)
        assert all(at == 0.0 for *_, at in eng._pending)
    finally:
        recorder.enable()


def test_a_token_that_joined_with_the_recorder_off_is_not_counted(recorder):
    eng = InferenceEngine(TinyLM(), EngineConfig(block_size=4, num_blocks=64))
    stream = eng.submit([3, 4, 5, 6], 8)
    recorder.disable()
    try:
        eng.step()                # prefill and the first decode step
        assert len(eng._pending) == 1
    finally:
        recorder.enable()
    _drive(eng)
    assert len(stream.tokens_so_far()) == 8
    seconds, tokens = _pending_wait(eng)
    # Seven decode tokens in all; the one that joined unstamped is left
    # out and adds no time since the clock's zero.
    assert tokens == 6 and 0 < seconds < 60
