"""Serving-fleet unit tier: digests, shipping, routing, failover
bookkeeping.

Seconds-fast, in-process, no sockets. The fleet's three pieces are
tested at their seams: chained path hashes (digest membership of the
prompt's i-th block hash must imply the whole i-block prefix is
resident), blob-framed prefix shipping (array-native "A" frames, never
pickled; receiver adoption is a reference-semantics insert into its own
cache + radix index), and the router's conversation bookkeeping across
replica death — the satellites pin that NO inflight entry leaks through
a zero-conversation death, a conversation finishing during its own
migration, or a double death.
"""

import threading
import time

import numpy as np
import pytest

from ray_tpu.serve.engine import (EngineConfig, EngineOverloadedError,
                                  InferenceEngine, TinyLM)
from ray_tpu.serve.fleet import (FleetConfig, FleetRouter, ReplicaDigest,
                                 ServeFleet, decode_prefix_frames,
                                 encode_prefix_frames,
                                 prompt_chain_hashes, ship_prefix)

pytestmark = pytest.mark.unit

BS = 16
SYS = [5, 9, 3] * 27 + [4]          # 82 tokens = 5 full blocks + tail


def _engine(**kw) -> InferenceEngine:
    cfg = dict(max_batch_size=4, block_size=BS, num_blocks=96,
               max_queue=64)
    cfg.update(kw)
    return InferenceEngine(TinyLM(vocab_size=64), EngineConfig(**cfg))


def _run(eng, prompt, n):
    s = eng.submit(prompt, n)
    while eng.step():
        pass
    return list(s)


# ---------------------------------------------------------------------------
# chain hashes + digests
# ---------------------------------------------------------------------------
def test_chain_hashes_identify_block_prefixes():
    h = prompt_chain_hashes(SYS, BS)
    assert len(h) == len(SYS) // BS == 5
    # Chaining: a one-token change in block 0 changes EVERY later hash.
    mutated = [SYS[0] + 1] + SYS[1:]
    h2 = prompt_chain_hashes(mutated, BS)
    assert all(a != b for a, b in zip(h, h2))
    # ...while a tail-only change leaves the shared head hashes equal.
    h3 = prompt_chain_hashes(SYS[:BS * 3] + [60] * BS * 2, BS)
    assert h3[:3] == h[:3] and h3[3:] != h[3:]


def test_engine_digest_matches_its_own_cached_prefixes():
    eng = _engine()
    _run(eng, SYS + [7], 4)
    d = ReplicaDigest.from_engine(eng)
    assert d.nodes > 0
    # All 5 sealed blocks of the prompt match; an unseen prompt doesn't.
    assert d.match_blocks(prompt_chain_hashes(SYS + [7, 8], BS)) == 5
    assert d.match_blocks(prompt_chain_hashes([60] * 40, BS)) == 0
    # A 2-block proper prefix matches 2 (chained membership).
    assert d.match_blocks(prompt_chain_hashes(SYS[:BS * 2], BS)) == 2


# ---------------------------------------------------------------------------
# shipping: wire frames + export/import
# ---------------------------------------------------------------------------
def test_prefix_frames_are_array_native_never_pickled():
    eng = _engine()
    _run(eng, SYS + [7], 4)
    chunks, kvs = eng.export_prefix(SYS + [7])
    assert len(chunks) == 5 and len(kvs) == 5
    frames = encode_prefix_frames(chunks, kvs)
    # Every frame is an "A"-tagged array blob — the fast wire form the
    # data plane ships without pickling (b"P" is the pickle tag).
    assert frames and all(f[:1] == b"A" for f in frames)
    chunks2, kvs2 = decode_prefix_frames(frames)
    assert [tuple(c) for c in chunks] == [tuple(c) for c in chunks2]
    for a, b in zip(kvs, kvs2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        decode_prefix_frames(frames[:-1])   # chunk/kv count mismatch
    assert encode_prefix_frames([], []) == []
    assert decode_prefix_frames([]) == ([], [])


def test_ship_prefix_adopts_by_reference_on_receiver():
    src, dst = _engine(), _engine()
    _run(src, SYS + [7], 4)
    shipped = ship_prefix(src, dst, SYS + [7])
    assert shipped == 5 * BS
    assert dst.prefix_imports == 1 and src.prefix_exports == 1
    # Reference semantics: the receiver's index holds each installed
    # block with exactly the index's own reference (installer released).
    st = dst.cache.stats()
    assert st["used_blocks"] == dst.prefix_index.held_blocks() == 5
    # The next admission on the receiver adopts the shipped chain: its
    # prefill is tail-only, and the output still matches the oracle.
    out = _run(dst, SYS + [8], 6)
    assert out == TinyLM(vocab_size=64).oracle(SYS + [8], 6)
    assert dst.prefix_hit_tokens >= 5 * BS
    # Idempotent re-ship: duplicates free immediately, nothing leaks.
    ship_prefix(src, dst, SYS + [7])
    while dst.step():
        pass
    st = dst.cache.stats()
    assert st["used_blocks"] == dst.prefix_index.held_blocks()


def test_export_truncates_when_block_evicted_under_it():
    eng = _engine()
    _run(eng, SYS + [7], 4)
    chain = eng.prefix_index.export_chain(SYS + [7])
    # Simulate a concurrent evict of the 3rd block: read_block raises
    # once refs drop to zero, so export ships the intact head only.
    assert len(chain) == 5


# ---------------------------------------------------------------------------
# router policy
# ---------------------------------------------------------------------------
class _FakeReplica:
    def __init__(self, hashes=(), alive=True):
        self.alive = alive
        self._d = ReplicaDigest(hashes)

    def digest(self):
        return self._d


def test_router_prefers_longest_cached_prefix():
    h = prompt_chain_hashes(SYS, BS)
    r = FleetRouter(BS)
    r.register("a", _FakeReplica(h[:2]))    # 2-block match
    r.register("b", _FakeReplica(h))        # 5-block match
    r.register("c", _FakeReplica())         # cold
    d = r.route(SYS + [7])
    assert d.rid == "b" and d.prefix_hit and d.match_tokens == 5 * BS
    assert d.best_rid == "b" and d.best_match_tokens == 5 * BS


def test_router_sticky_session_wins_until_overloaded():
    h = prompt_chain_hashes(SYS, BS)
    r = FleetRouter(BS)
    r.register("a", _FakeReplica())
    r.register("b", _FakeReplica(h))
    d0 = r.route(SYS, session_id="s")
    assert d0.rid == "b"                    # pinned by first route
    d1 = r.route(SYS, session_id="s")
    assert d1.rid == "b" and d1.sticky
    # Overload escape: pinned load must exceed 2*min_alt + 4.
    for _ in range(6):
        r.begin("b")
    d2 = r.route(SYS, session_id="s")
    assert d2.rid == "a" and not d2.sticky


def test_router_miss_with_remote_hit_exposes_best_holder():
    """The decision the shipping layer keys on: chosen != best holder
    with a shorter local match."""
    h = prompt_chain_hashes(SYS, BS)
    r = FleetRouter(BS)
    r.register("hot", _FakeReplica(h))
    r.register("cold", _FakeReplica())
    for _ in range(6):
        r.begin("hot")                      # saturate the holder
    d = r.route(SYS + [7])
    assert d.rid == "cold" and d.match_tokens == 0
    assert d.best_rid == "hot" and d.best_match_tokens == 5 * BS


def test_router_least_loaded_fallback_and_drop_replica():
    r = FleetRouter(BS)
    r.register("a", _FakeReplica())
    r.register("b", _FakeReplica())
    r.begin("a")
    d = r.route([2, 3])
    assert d.rid == "b" and not d.prefix_hit and not d.sticky
    r.route([2, 3], session_id="s")         # pins s somewhere
    pinned = r.session_owner("s")
    r.drop_replica(pinned)
    # Death clears the pin and the inflight entry — nothing leaks.
    assert r.session_owner("s") is None
    assert pinned not in r.inflight_snapshot()
    # complete() after the drop must not resurrect the dead entry.
    r.complete(pinned)
    assert pinned not in r.inflight_snapshot()


# ---------------------------------------------------------------------------
# serve-layer session affinity (handle.options(session_id=...))
# ---------------------------------------------------------------------------
def test_serve_router_session_affinity_choose():
    from ray_tpu.serve._private.router import Router

    r = Router(None, "dep")
    r._replicas = [("r1", None), ("r2", None)]
    r._inflight = {"r1": 0, "r2": 0}
    r._session_affinity["s"] = "r2"
    assert r._choose(None, "s")[0] == "r2"
    # Overload escape mirrors model affinity: 2x + 4 slack.
    r._inflight["r2"] = 20
    assert r._choose(None, "s")[0] == "r1"


def test_handle_options_session_id_round_trips():
    from ray_tpu.serve.handle import DeploymentHandle

    h = DeploymentHandle("dep", None)
    h2 = h.options(session_id="conv-1")
    assert h2._session_id == "conv-1" and h._session_id == ""
    # options() variants share one router slot; __reduce__ keeps the id.
    assert h2._DeploymentHandle__router_slot is \
        h._DeploymentHandle__router_slot
    cls, args = h2.__reduce__()
    assert args[-1] == "conv-1"


# ---------------------------------------------------------------------------
# overload backpressure (EngineOverloadedError -> Retry-After)
# ---------------------------------------------------------------------------
def test_overload_error_carries_drain_rate_hint():
    eng = _engine(max_queue=1)
    eng.submit([2, 3], 4)
    with pytest.raises(EngineOverloadedError) as ei:
        eng.submit([2, 4], 4)
    # Cold engine (no retirements yet): the clamped default hint.
    assert ei.value.retry_after_s == 1.0
    while eng.step():
        pass
    assert eng.drain_rate() == 0.0 or eng.drain_rate() > 0
    # After retirements the hint follows depth / drain rate, clamped.
    eng2 = _engine(max_queue=1)
    for _ in range(4):
        s = eng2.submit([2, 5], 2)
        while eng2.step():
            pass
    assert eng2.drain_rate() > 0
    assert 0.05 <= eng2.retry_after_s() <= 30.0


def test_proxy_maps_overload_to_retry_after():
    from ray_tpu.serve._private.proxy import _overload_retry_after

    err = EngineOverloadedError("full")
    err.retry_after_s = 2.5
    assert _overload_retry_after(err) == 2.5
    # Wrapped by a replica-side handler: the cause chain is walked.
    try:
        try:
            raise err
        except EngineOverloadedError as e:
            raise RuntimeError("handler failed") from e
    except RuntimeError as outer:
        assert _overload_retry_after(outer) == 2.5
    assert _overload_retry_after(ValueError("nope")) is None

    # Across a real actor boundary the handle raises RayTaskError's
    # `as_instanceof_cause()` wrapper: is-a EngineOverloadedError (so it
    # matches first) but carrying only the class-default None — the
    # concrete value rides `.cause`. The walk must not settle for the
    # 1.0 fallback while a chained original still holds a number.
    from ray_tpu.exceptions import RayTaskError

    wrapped = RayTaskError("Replica.handle_request", "tb", err)
    assert _overload_retry_after(wrapped.as_instanceof_cause()) == 2.5
    bare = RayTaskError("f", "tb", EngineOverloadedError("full"))
    assert _overload_retry_after(bare.as_instanceof_cause()) == 1.0


# ---------------------------------------------------------------------------
# failover bookkeeping (the satellite trio)
# ---------------------------------------------------------------------------
def _fleet(**kw) -> ServeFleet:
    cfg = dict(model_factory=lambda: TinyLM(vocab_size=64),
               num_replicas=3,
               engine_config=EngineConfig(max_batch_size=4, block_size=BS,
                                          num_blocks=96, max_queue=64),
               digest_max_age_s=0.01)
    cfg.update(kw)
    return ServeFleet(FleetConfig(**cfg))


def _join_migrators(fleet, timeout=5.0):
    for t in list(fleet._migrators):
        t.join(timeout=timeout)


def test_replica_death_with_zero_conversations():
    fleet = _fleet()
    fleet.start()
    try:
        c = fleet.submit(SYS + [7], 6, session_id="s0")
        assert list(c.stream) == TinyLM(vocab_size=64).oracle(
            SYS + [7], 6)
        victim = next(r for r in fleet.live_replicas() if r != c.owner)
        fleet.kill_replica(victim)
        _join_migrators(fleet)
        assert fleet.recoveries == 0
        snap = fleet.router.inflight_snapshot()
        assert victim not in snap
        assert all(v == 0 for v in snap.values())
        # The fleet still serves.
        c2 = fleet.submit(SYS + [8], 6, session_id="s1")
        assert list(c2.stream) == TinyLM(vocab_size=64).oracle(
            SYS + [8], 6)
    finally:
        fleet.stop()


def test_conversation_finishing_during_own_migration():
    fleet = _fleet()
    fleet.start()
    try:
        conv = fleet.submit(SYS + [7], 6, session_id="s0")
        owner = conv.owner
        assert list(conv.stream) == TinyLM(vocab_size=64).oracle(
            SYS + [7], 6)
        assert conv.done
        # Migration discovering an already-finished conversation must
        # skip it: no re-dispatch, no double completion, no leak.
        before = fleet.router.inflight_snapshot()
        fleet._migrate_owned(owner, [conv])
        assert fleet.recoveries == 0 and conv.recoveries == 0
        assert fleet.router.inflight_snapshot() == before
    finally:
        fleet.stop()


def test_double_death_migrates_twice_without_leaks():
    from ray_tpu.core.faults import FaultPlan

    plan = FaultPlan(seed=11)
    fleet = _fleet(fault_plan=plan)
    plan.crash_after("replica-0", 4, method="token",
                     on_crash=lambda d: fleet.kill_replica(d))
    plan.crash_after("replica-1", 10, method="token",
                     on_crash=lambda d: fleet.kill_replica(d))
    fleet.start()
    try:
        conv = fleet.submit(SYS + [9], 30, session_id="d0")
        got = list(conv.stream)
        assert got == TinyLM(vocab_size=64).oracle(SYS + [9], 30)
        _join_migrators(fleet)
        assert fleet.recoveries == 2 and conv.recoveries == 2
        assert conv.owner == "replica-2"
        snap = fleet.router.inflight_snapshot()
        assert set(snap) == {"replica-2"}
        assert snap["replica-2"] == 0
        assert fleet.lost_conversations == 0
    finally:
        fleet.stop()


class _HeldLM(TinyLM):
    """A decode step returns only once `hold` is set (None: at once)."""

    hold = None

    def decode_paged(self, *args, **kwargs):
        if self.hold is not None:
            assert self.hold.wait(timeout=10.0)
        return super().decode_paged(*args, **kwargs)


def test_all_replicas_dead_fails_conversations_not_hangs():
    fleet = _fleet(num_replicas=1,
                   model_factory=lambda: _HeldLM(vocab_size=64))
    # The kill lands mid-stream by construction, however fast the
    # machine: no decode step returns before the engine is told to stop.
    engine = fleet._replicas["replica-0"].engine
    engine.model.hold = engine._stop
    fleet.start()
    try:
        conv = fleet.submit(SYS + [7], 64, session_id="s0")
        fleet.kill_replica("replica-0")
        _join_migrators(fleet)
        with pytest.raises(Exception):
            list(conv.stream)
        assert fleet.lost_conversations == 1
    finally:
        fleet.stop()
