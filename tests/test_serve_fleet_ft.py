"""Serving-fleet fault tolerance: the PR-19 acceptance scenario.

Three in-process `InferenceEngine` replicas behind the KV-cache-aware
`ServeFleet` router serve a burst of conversations sharing one system
prompt; a seeded `core/faults.py` crash rule kills a replica mid-decode
(`crash_after(rid, n, "token")` — the replica dies on its nth streamed
token, deterministic per seed). The subsystem must then prove:

- every in-flight conversation completes on a survivor token-for-token
  equal to the no-fault run (`TinyLM.oracle` — the engine's equality to
  it is pinned by the unit engine tier, so the oracle IS the no-fault
  reference);
- no survivor leaks KV blocks (allocated == index-held on every
  survivor once the fleet drains: every conversation's private tail was
  freed, only sealed shared prefixes remain);
- cross-replica prefix shipping engaged (`fleet_prefix_ships > 0`) —
  the overload spill that spreads the burst ships the sealed prompt
  chain ahead of each spilled conversation;
- the router's bookkeeping survives: no inflight entry for the dead
  replica, zero residual inflight anywhere, zero lost conversations.
"""

import threading
import time

import pytest

from ray_tpu.core.faults import FaultPlan
from ray_tpu.serve.engine import EngineConfig, TinyLM
from ray_tpu.serve.fleet import FleetConfig, ServeFleet

pytestmark = pytest.mark.unit

BS = 16
SYS = [7 + (i % 19) for i in range(80)]     # 5 sealed blocks


def _config(plan=None) -> FleetConfig:
    return FleetConfig(
        model_factory=lambda: TinyLM(vocab_size=64,
                                     step_delay_s=0.001),
        num_replicas=3,
        engine_config=EngineConfig(max_batch_size=8, block_size=BS,
                                   num_blocks=160, max_queue=128),
        digest_max_age_s=0.01,
        fault_plan=plan)


def _hold_steps(fleet, gate) -> None:
    """Every replica's loop waits at the top of an iteration while
    `gate` is shut: outside the cache lock, so routing and shipping go
    on meanwhile."""
    for rid in fleet.live_replicas():
        eng = fleet.replica(rid).engine
        eng.step = lambda step=eng.step: gate.wait(10.0) and step()


def test_replica_kill_mid_decode_recovers_every_conversation():
    plan = FaultPlan(seed=19)
    fleet = ServeFleet(_config(plan))
    gate = threading.Event()
    gate.set()
    _hold_steps(fleet, gate)

    kill_stamp = []

    def kill(dst):
        kill_stamp.append(time.perf_counter())
        fleet.kill_replica(dst)

    # The warm-up conversation streams 4 tokens into replica-0 first,
    # so the 30th token-credit lands well inside the burst's decode.
    plan.crash_after("replica-0", 30, method="token", on_crash=kill)
    fleet.start()
    try:
        warm = fleet.submit(SYS + [2, 3, 4], 4, session_id="warmup")
        for _ in warm.stream:
            pass
        time.sleep(0.05)            # holder digest publishes

        prompts = [SYS + [2 + (i % 9), 3 + (i % 5), 4 + (i % 7)]
                   for i in range(8)]
        # No engine iterates until the whole burst is in: the holder
        # stays alive and loaded while the router spills (and ships),
        # and the kill lands mid-burst, however slow this thread is.
        gate.clear()
        convs = [fleet.submit(p, 24, session_id=f"s{i}")
                 for i, p in enumerate(prompts)]
        gate.set()
        oracle = TinyLM(vocab_size=64)
        for p, c in zip(prompts, convs):
            assert list(c.stream) == oracle.oracle(p, 24), \
                f"{c.conv_id} diverged from the no-fault run"

        # The kill actually happened, mid-burst, and recovery engaged.
        assert kill_stamp, "seeded crash never fired"
        assert "replica-0" not in fleet.live_replicas()
        assert fleet.recoveries >= 1
        assert fleet.lost_conversations == 0

        # Shipping engaged while the burst spilled across replicas.
        assert fleet.prefix_ships > 0
        assert fleet.prefix_ship_tokens >= 5 * BS

        # Router bookkeeping: the dead replica's inflight entry is gone
        # and nothing residual is counted anywhere.
        for t in list(fleet._migrators):
            t.join(timeout=5.0)
        snap = fleet.router.inflight_snapshot()
        assert "replica-0" not in snap
        assert all(v == 0 for v in snap.values()), snap

        # Zero leaked KV blocks on every survivor: once the engines
        # drain, every allocated block is held by the prefix index
        # (free + index-held == total) — conversations freed their
        # private tails, recovery re-prefills included.
        assert fleet.drain(10.0)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            leaks = []
            for rid in fleet.live_replicas():
                eng = fleet.replica(rid).engine
                if not eng.drain(0.1):
                    leaks.append(rid)
                    continue
                st = eng.cache.stats()
                if st["used_blocks"] != eng.prefix_index.held_blocks():
                    leaks.append((rid, st["used_blocks"],
                                  eng.prefix_index.held_blocks()))
            if not leaks:
                break
            time.sleep(0.02)
        assert not leaks, f"leaked KV blocks: {leaks}"

        # The fleet-layer counters made it to the metrics registry
        # (the dashboard's /api/serve fleet section reads these).
        from ray_tpu.util.metrics import default_registry

        snap_m = {m["name"]: m for m in default_registry().snapshot()}
        ships = snap_m.get("serve_fleet_prefix_ships")
        assert ships is not None
        assert sum(s["value"] for s in ships["samples"]) > 0
    finally:
        fleet.stop()


def test_fault_schedule_is_replayable():
    """Same seed, same workload -> same kill point and same recovery
    outcome (the faults.py determinism contract extended through the
    fleet): both runs die on the identical token index and both recover
    to the identical streams."""
    outcomes = []
    for _ in range(2):
        plan = FaultPlan(seed=23)
        fleet = ServeFleet(_config(plan))
        plan.crash_after("replica-0", 12, method="token",
                         on_crash=lambda d: fleet.kill_replica(d))
        fleet.start()
        try:
            conv = fleet.submit(SYS + [5], 32, session_id="r")
            got = list(conv.stream)
            for t in list(fleet._migrators):
                t.join(timeout=5.0)
            outcomes.append((got, fleet.recoveries,
                             [a.key() for a in plan.log
                              if a.kind == "crash"]))
        finally:
            fleet.stop()
    assert outcomes[0] == outcomes[1]
    assert outcomes[0][1] == 1                  # recovery happened
    assert outcomes[0][0] == TinyLM(vocab_size=64).oracle(SYS + [5], 32)
