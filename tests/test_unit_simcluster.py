"""Control-plane survival at 100 nodes — the simulated-raylet harness.

ISSUE 14 acceptance: a 100-node simulated cluster survives a seeded
fault schedule (GCS kill -9 + 10% raylet crashes + 1% message drops)
with zero lost tasks, zero leaked placement-group reservations, and
full re-registration after restart; the same seed reproduces the
identical fault schedule.

Everything here runs real control-plane code — `GcsServer` handlers,
`NodeLedger` 2PC, `schedule_placement_group`, the heartbeat/re-register
contract — over in-process loopback dispatch (`core/simcluster.py`),
in one pytest process, in seconds.
"""

import asyncio
import os

import pytest

pytestmark = pytest.mark.unit


def _run(coro, timeout=120):
    return asyncio.run(asyncio.wait_for(coro, timeout))


# ---------------------------------------------------------------------------
# fault plan determinism
# ---------------------------------------------------------------------------

def test_fault_schedule_is_a_pure_function_of_the_seed():
    from ray_tpu.core.faults import FaultPlan

    def build(seed):
        p = FaultPlan(seed)
        p.drop(p=0.05)
        p.delay(method="heartbeat", p=0.1, delay_s=0.002)
        p.duplicate(method="request_sim_lease", p=0.1)
        return p

    a, b = build(17), build(17)
    sched_a = a.preview("driver", "simnode0001", "request_sim_lease", 500)
    sched_b = b.preview("driver", "simnode0001", "request_sim_lease", 500)
    assert [x.key() for x in sched_a] == [x.key() for x in sched_b]
    assert sched_a, "a 5%+10% plan over 500 messages must fault sometimes"

    # A different seed yields a different schedule...
    c = build(18)
    sched_c = c.preview("driver", "simnode0001", "request_sim_lease", 500)
    assert [x.key() for x in sched_a] != [x.key() for x in sched_c]
    # ...and decisions are edge-local: another edge differs too.
    sched_d = a.preview("driver", "simnode0002", "request_sim_lease", 500)
    assert [x.key() for x in sched_a] != [x.key() for x in sched_d]


def test_fault_plan_drop_delay_duplicate_partition_semantics():
    from ray_tpu.core.faults import FaultInjected, FaultPlan
    from ray_tpu.core.rpc import ConnectionLost

    async def scenario():
        plan = FaultPlan(seed=3)
        cut = plan.partition("a", "b")
        with pytest.raises(ConnectionLost):
            await plan.apply("a", "b", "ping")        # one-way: a->b cut
        assert not await plan.apply("b", "a", "ping")  # reverse flows
        plan.heal(cut)
        assert not await plan.apply("a", "b", "ping")

        dup = FaultPlan(seed=3)
        dup.duplicate(p=1.0)
        assert await dup.apply("a", "b", "x") is True

        crash = FaultPlan(seed=3)
        crashed = []
        crash.crash_after("b", 3, on_crash=crashed.append)
        await crash.apply("a", "b", "m")
        await crash.apply("c", "b", "m")
        with pytest.raises(FaultInjected):
            await crash.apply("a", "b", "m")  # b's 3rd received message
        assert crashed == ["b"]
        # the rule fires once
        assert not await crash.apply("a", "b", "m")

    _run(scenario())


def test_faults_hook_into_real_rpc_dispatch():
    """The rpc.py server hook: a drop rule swallows the request (caller
    sees no reply), a duplicate rule dispatches the handler twice."""
    from ray_tpu.core import faults
    from ray_tpu.core.rpc_testing import LoopbackClient

    class Handlers:
        def __init__(self):
            self.calls = 0

        async def handle_bump(self, conn):
            self.calls += 1
            return self.calls

    async def scenario():
        h = Handlers()
        client = LoopbackClient(h)
        await client.connect()
        plan = faults.FaultPlan(seed=0)
        plan.duplicate(method="bump", p=1.0, end=1)   # first call only
        plan.drop(method="bump", p=1.0, start=1, end=2)  # second call
        faults.install(plan)
        try:
            # The genuine dispatch answers; the duplicate redelivery
            # runs concurrently with its reply discarded.
            assert await client.call("bump") == 1
            for _ in range(5):                      # let the dup land
                await asyncio.sleep(0)
            assert h.calls == 2
            with pytest.raises(Exception):
                await client.call("bump")           # dropped: no reply
            assert h.calls == 2
            assert await client.call("bump") == 3   # clean again
        finally:
            faults.uninstall()

    _run(scenario())


# ---------------------------------------------------------------------------
# gcs client backoff
# ---------------------------------------------------------------------------

def test_reconnect_backoff_full_jitter_bounds():
    import random

    from ray_tpu.core.config import ray_config
    from ray_tpu.core.gcs.client import backoff_delay

    cfg = ray_config()
    saved = dict(cfg._values)
    cfg.apply_system_config({"gcs_reconnect_backoff_base_ms": 100.0,
                             "gcs_reconnect_backoff_max_ms": 1000.0})
    try:
        rng = random.Random(0)
        for attempt in range(20):
            ceiling = min(1.0, 0.1 * 2 ** attempt)
            for _ in range(50):
                d = backoff_delay(attempt, rng)
                assert 0.0 <= d <= ceiling + 1e-9
        # FULL jitter: the low end of the range is actually used (a
        # "equal jitter" regression would floor at ceiling/2).
        lows = sum(backoff_delay(6, rng) < 0.5 for _ in range(200))
        assert lows > 40
    finally:
        cfg._values.clear()
        cfg._values.update(saved)


def test_reconnecting_rpc_sleeps_with_jitter(monkeypatch):
    """_ReconnectingRpc._reconnect consults backoff_delay instead of the
    old fixed 0.5 s sleep — pinned by substituting both the sleep and
    the dial so no socket is ever opened."""
    from ray_tpu.core.config import ray_config
    from ray_tpu.core.gcs import client as gcs_client
    from ray_tpu.core.rpc import ConnectionLost

    cfg = ray_config()
    saved = dict(cfg._values)
    cfg.apply_system_config({"gcs_rpc_timeout_s": 0.4,
                             "gcs_reconnect_backoff_base_ms": 40.0,
                             "gcs_reconnect_backoff_max_ms": 120.0})

    sleeps = []

    async def fake_sleep(d):
        sleeps.append(d)

    class DeadClient:
        def __init__(self, address):
            self.connected = False

        async def connect(self, timeout=10.0):
            raise OSError("connection refused")

        async def close(self):
            pass

    async def scenario():
        rpc = gcs_client._ReconnectingRpc("127.0.0.1:1")
        rpc._client = DeadClient("127.0.0.1:1")
        rpc._reconnect_lock = asyncio.Lock()
        monkeypatch.setattr(gcs_client, "RpcClient", DeadClient)
        monkeypatch.setattr(gcs_client.asyncio, "sleep", fake_sleep)
        with pytest.raises(ConnectionLost):
            await rpc._reconnect()

    try:
        _run(scenario())
    finally:
        cfg._values.clear()
        cfg._values.update(saved)
    # fake_sleep never advances the loop clock, so the window closes on
    # wall time spent dialing; at least a few attempts must have slept,
    # each within the jitter ceiling and not all identical (jitter).
    assert len(sleeps) >= 2
    assert all(0.0 <= s <= 0.12 + 1e-9 for s in sleeps)
    assert len(set(sleeps)) > 1


# ---------------------------------------------------------------------------
# scale: registration, heartbeats, scheduling
# ---------------------------------------------------------------------------

def test_100_nodes_register_heartbeat_and_schedule(tmp_path):
    from ray_tpu.core.simcluster import SimCluster

    async def scenario():
        cluster = SimCluster(num_nodes=100, seed=5)
        await cluster.start()
        try:
            assert await cluster.wait_until(
                lambda: cluster.registered_count() == 100, timeout=15)
            # Placement at scale, all four strategies on the real
            # select_pg_nodes + 2PC.
            for strategy in ("PACK", "SPREAD", "STRICT_PACK",
                             "STRICT_SPREAD"):
                pg_id, state = await cluster.driver.create_placement_group(
                    [{"CPU": 1.0}] * 4, strategy=strategy)
                assert state == "CREATED", (strategy, state)
            # Tasks spread across the fleet.
            results = await asyncio.gather(
                *(cluster.driver.submit_task() for _ in range(200)))
            assert all(results)
            assert not cluster.driver.lost
            grants = sum(r.lease_grants
                         for r in cluster.raylets.values())
            assert grants >= 200
        finally:
            await cluster.stop()

    _run(scenario())


def test_pg_rolls_back_when_a_raylet_dies_mid_reserve(tmp_path):
    """A raylet crash between prepare and commit must roll back the
    partial reservations on every OTHER node — the capacity-leak class
    the 2PC exists to prevent."""
    from ray_tpu.core.faults import FaultPlan
    from ray_tpu.core.simcluster import SimCluster

    async def scenario():
        plan = FaultPlan(seed=11)
        # The victim dies when its first prepare_bundle arrives: with
        # STRICT_SPREAD over 4 bundles, up to 3 other nodes already
        # hold a prepared reservation at that instant.
        plan.crash_after("simnode0000", 1, method="prepare_bundle")
        cluster = SimCluster(num_nodes=8, seed=11, plan=plan,
                             resources={"CPU": 2.0})
        await cluster.start()
        try:
            assert await cluster.wait_until(
                lambda: cluster.registered_count() == 8, timeout=10)
            # Force the victim into every placement: all 8 nodes needed.
            pg_id, state = await cluster.driver.create_placement_group(
                [{"CPU": 2.0}] * 8, strategy="STRICT_SPREAD", attempts=2)
            # 7 nodes can't hold 8 STRICT_SPREAD bundles.
            assert state == "INFEASIBLE"
            assert await cluster.wait_until(
                lambda: not cluster.leaked_reservations()
                and not cluster.resource_violations(), timeout=10), (
                cluster.leaked_reservations(),
                cluster.resource_violations())
        finally:
            await cluster.stop()

    _run(scenario())


def test_gcs_restart_grace_no_false_deaths_then_real_deaths(tmp_path):
    """After a GCS kill -9 + restart, recovered nodes are NOT declared
    dead inside the grace window (no false node-death storm), but a
    node that truly died during the outage IS declared dead once the
    grace passes."""
    from ray_tpu.core.simcluster import SimCluster

    async def scenario():
        path = os.path.join(tmp_path, "gcs.pkl")
        cluster = SimCluster(num_nodes=30, seed=2, storage_path=path)
        await cluster.start()
        try:
            assert await cluster.wait_until(
                lambda: cluster.registered_count() == 30, timeout=10)
            # Let the 1 Hz debounce persist the node table.
            await asyncio.sleep(1.2)
            cluster.kill_gcs()
            cluster.crash_raylet("simnode0005")  # dies during the outage
            await asyncio.sleep(0.5)
            await cluster.restart_gcs()
            # Recovery: the persisted membership table is live
            # immediately, stale-marked, inside the grace window.
            recovered = [n for n in cluster.gcs.nodes.values()
                         if n.get("alive")]
            assert len(recovered) == 30
            assert all(n.get("stale_view") for n in recovered)
            # Survivors reconcile via their first heartbeat (no
            # re-register storm: was_dead never fires), the real death
            # is detected after the grace.
            assert await cluster.wait_until(
                lambda: cluster.registered_count() == 29, timeout=10)
            survivors = [n for n in cluster.gcs.nodes.values()
                         if n.get("alive")]
            assert not any(n.get("stale_view") for n in survivors)
            dead = cluster.gcs.nodes["simnode0005"]
            assert not dead["alive"]
        finally:
            await cluster.stop()

    _run(scenario())


def test_committed_bundles_of_lost_groups_are_reconciled(tmp_path):
    """Owner dies between commit and the CREATED CAS: the group stays
    PENDING forever, and the raylet-side reconciler must return the
    committed reservations after pg_stuck_commit_s."""
    from ray_tpu.core.simcluster import SimCluster

    async def scenario():
        cluster = SimCluster(num_nodes=4, seed=9,
                             config={"pg_stuck_commit_s": 0.5})
        await cluster.start()
        try:
            assert await cluster.wait_until(
                lambda: cluster.registered_count() == 4, timeout=10)
            # Drive the 2PC by hand up to (and including) commit, then
            # "die" before the CAS.
            drv = cluster.driver
            pg_id = "simpgorphan"
            await drv._gcs.register_placement_group(pg_id, {
                "bundles": [{"CPU": 1.0}], "strategy": "PACK",
                "state": "PENDING", "owner": "driver",
                "target_node_ids": None})
            client = await drv.raylet_client_for("sim:simnode0000")
            r = await client.call("prepare_bundle", pg_id=pg_id,
                                  bundle_index=0, resources={"CPU": 1.0})
            assert r["ok"]
            assert await client.call("commit_bundle", pg_id=pg_id,
                                     bundle_index=0)
            victim = cluster.raylets["simnode0000"]
            assert any(b.committed for b in victim._bundles.values())
            # No CAS ever arrives. The reconciler returns the orphan.
            assert await cluster.wait_until(
                lambda: not victim._bundles, timeout=10)
            assert victim.resources_available == victim.resources_total
        finally:
            await cluster.stop()

    _run(scenario())


def test_schedule_pg_rolls_back_committed_bundles_when_cas_fails():
    """Review regression: an exception from the CREATED CAS must reach
    the attempt's rollback — an escaped one used to strand every
    committed bundle (invisible to the reconciler once a later attempt
    succeeded on other nodes). And a CAS whose ack was lost but whose
    write APPLIED must be recognized on re-read, not rolled back."""
    from ray_tpu.core.cluster_runtime import schedule_placement_group
    from ray_tpu.core.rpc import ConnectionLost

    class FakeRaylet:
        def __init__(self, log):
            self.log = log

        async def call(self, method, timeout=None, **kw):
            self.log.append((method, kw.get("bundle_index")))
            if method == "prepare_bundle":
                return {"ok": True}
            return True

    class FakeGcs:
        def __init__(self, cas_mode):
            self.state = "PENDING"
            self.cas_mode = cas_mode  # "raise" | "lost_ack"

        async def get_placement_group(self, pg_id):
            return {"state": self.state}

        async def get_nodes(self):
            return [{"node_id": "n1", "alive": True, "address": "a1",
                     "resources_available": {"CPU": 8.0}}]

        async def update_placement_group(self, pg_id, updates,
                                         expect_state=None):
            if updates.get("state") == "CREATED":
                if self.cas_mode == "raise":
                    raise ConnectionLost("gcs gone")
                # lost_ack: the write APPLIES but the reply is lost —
                # modeled as False now, CREATED visible on re-read.
                self.state = "CREATED"
                return False
            if expect_state is not None and self.state != expect_state:
                return False
            self.state = updates["state"]
            return True

    async def scenario():
        # Arm 1: CAS raises every time -> every committed bundle must be
        # returned, and the group ends INFEASIBLE.
        log = []
        gcs = FakeGcs("raise")

        async def client_for(addr):
            return FakeRaylet(log)

        info = {"bundles": [{"CPU": 1.0}] * 2, "strategy": "PACK",
                "target_node_ids": None}
        state = await schedule_placement_group(gcs, client_for, "pgx",
                                               info, attempts=2)
        assert state == "INFEASIBLE"
        commits = [i for m, i in log if m == "commit_bundle"]
        returns = [i for m, i in log if m == "return_bundle"]
        assert commits and sorted(returns) == sorted(commits), log

        # Arm 2: the CAS ack is lost but the write applied -> re-read
        # sees CREATED; no rollback, success reported.
        log2 = []
        gcs2 = FakeGcs("lost_ack")

        async def client_for2(addr):
            return FakeRaylet(log2)

        state = await schedule_placement_group(gcs2, client_for2, "pgy",
                                               info, attempts=2)
        assert state == "CREATED"
        assert not [m for m, _ in log2 if m == "return_bundle"], log2

    _run(scenario())


# ---------------------------------------------------------------------------
# data-plane recovery (round 15): lineage reconstruction + PG rescheduling
# ---------------------------------------------------------------------------

def test_pg_reschedules_onto_survivors_when_member_node_dies():
    """A CREATED group whose member node dies returns to CREATED on the
    survivors: the GCS CAS-transitions it to RESCHEDULING, re-places
    ONLY the lost bundle through the 2PC (surviving bundles keep their
    reservations — same nodes, untouched ledgers), and the terminal CAS
    lands the merged location table. Zero leaked reservations after,
    and the recovery is pinned in the flight ring (`pg.reschedule`)."""
    from ray_tpu.core import flight
    from ray_tpu.core.faults import FaultPlan
    from ray_tpu.core.simcluster import SimCluster

    async def scenario():
        plan = FaultPlan(seed=23)
        plan.drop(p=0.01)
        cluster = SimCluster(num_nodes=8, seed=23, plan=plan)
        await cluster.start()
        try:
            assert await cluster.wait_until(
                lambda: cluster.registered_count() == 8, timeout=10)
            pg_id, state = await cluster.driver.create_placement_group(
                [{"CPU": 1.0}] * 3, strategy="STRICT_SPREAD")
            assert state == "CREATED"
            info = await cluster.driver._gcs.get_placement_group(pg_id)
            locs = [loc["node_id"] for loc in info["bundle_locations"]]
            victim, survivors = locs[1], {locs[0], locs[2]}
            cluster.crash_raylet(victim)

            def rescheduled():
                pg = cluster.gcs.placement_groups.get(pg_id) or {}
                cur = [loc["node_id"]
                       for loc in pg.get("bundle_locations") or []]
                return (pg.get("state") == "CREATED" and cur
                        and victim not in cur)

            assert await cluster.wait_until(rescheduled, timeout=15), (
                cluster.gcs.placement_groups.get(pg_id))
            pg = cluster.gcs.placement_groups[pg_id]
            cur = [loc["node_id"] for loc in pg["bundle_locations"]]
            # Survivors kept their exact placements; only the lost
            # bundle moved, onto a live node not already holding one
            # (STRICT_SPREAD).
            assert cur[0] == locs[0] and cur[2] == locs[2]
            assert cur[1] not in survivors and cur[1] != victim
            assert cluster.raylets[cur[1]].alive
            assert await cluster.wait_until(
                lambda: not cluster.leaked_reservations(), timeout=10), (
                cluster.leaked_reservations())
            # Surviving reservations really are untouched ledgers.
            for idx in (0, 2):
                node = cluster.raylets[cur[idx]]
                assert any(k.startswith(pg_id + ":")
                           for k in node._bundles), cur[idx]
            events = flight.dump(include_events=True)["events"]
            assert any(e[3] == "pg.reschedule" for e in events)
        finally:
            await cluster.stop()

    _run(scenario())


def test_borrower_get_survives_holder_node_death():
    """THE data-plane acceptance core: a borrower's get() of an object
    whose holder node died returns the correct value via lineage
    re-execution — no user-visible error — including RECURSIVE
    reconstruction of a dependency lost with its own node. The
    re-execution is pinned in the flight ring (`lineage.reexec`)."""
    from ray_tpu.core import flight
    from ray_tpu.core.faults import FaultPlan
    from ray_tpu.core.simcluster import SimCluster

    async def scenario():
        plan = FaultPlan(seed=31)
        plan.drop(p=0.01)
        cluster = SimCluster(num_nodes=8, seed=31, plan=plan)
        await cluster.start()
        try:
            assert await cluster.wait_until(
                lambda: cluster.registered_count() == 8, timeout=10)
            drv = cluster.driver
            borrower = cluster.add_driver("borrower")
            base = await drv.create_object("base")
            mid = await drv.create_object("mid", deps=[base])
            assert (await borrower.get_object(mid, owner="driver")
                    == "mid(base())")
            assert drv.exec_counts == {"base": 1, "mid": 1}
            # Kill every node holding a copy: the directory-listed
            # holders AND the borrower's local raylet (its store cached
            # the pulled copy — "the node holding the borrowed object").
            holders = (set(drv._objects[base]["nodes"])
                       | set(drv._objects[mid]["nodes"])
                       | {borrower.node, drv.node} - {None})
            for h in holders:
                cluster.crash_raylet(h)
            # Borrower blocks-and-retries through the re-execution and
            # lands the SAME deterministic value.
            assert (await borrower.get_object(mid, owner="driver",
                                              timeout=20)
                    == "mid(base())")
            assert drv.exec_counts["mid"] == 2
            if len(holders) > 1:
                # base's holder died too: mid's re-execution re-resolved
                # it, which reconstructed base first (recursive).
                assert drv.exec_counts["base"] == 2
            assert drv.lineage.stats()["reexecs"] >= 1
            events = flight.dump(include_events=True)["events"]
            assert any(e[3] == "lineage.reexec" for e in events)
        finally:
            await cluster.stop()

    _run(scenario())


def test_health_loop_rescues_created_group_on_silently_dead_node():
    """Review race: a node that dies while its group is mid-reschedule
    is skipped by _mark_node_dead's CREATED-only scan, so the pass can
    land CREATED with a location naming the fresh corpse. The health
    loop's CREATED-vs-live-node-table scan is the safety net — pinned
    here by marking the node dead WITHOUT the _mark_node_dead trigger
    (its alive guard then makes the scan the only recovery path)."""
    from ray_tpu.core.simcluster import SimCluster

    async def scenario():
        cluster = SimCluster(num_nodes=6, seed=19)
        await cluster.start()
        try:
            assert await cluster.wait_until(
                lambda: cluster.registered_count() == 6, timeout=10)
            pg_id, state = await cluster.driver.create_placement_group(
                [{"CPU": 1.0}] * 2, strategy="STRICT_SPREAD")
            assert state == "CREATED"
            info = cluster.gcs.placement_groups[pg_id]
            victim = info["bundle_locations"][0]["node_id"]
            # The exact post-race state: table says dead, group says
            # CREATED-on-victim, no death event ever fired for it.
            cluster.gcs.nodes[victim]["alive"] = False
            cluster.crash_raylet(victim)

            def rescued():
                pg = cluster.gcs.placement_groups.get(pg_id) or {}
                locs = [loc["node_id"]
                        for loc in pg.get("bundle_locations") or []]
                return (pg.get("state") == "CREATED" and locs
                        and victim not in locs)

            assert await cluster.wait_until(rescued, timeout=15), (
                cluster.gcs.placement_groups.get(pg_id))
            assert await cluster.wait_until(
                lambda: not cluster.leaked_reservations(), timeout=10)
        finally:
            await cluster.stop()

    _run(scenario())


def test_health_loop_does_not_charge_nodes_for_its_own_stall():
    """Opening a TPU client froze every process of the chip machine for
    8 s; the GCS woke, read the silence as five missed heartbeats, and
    reaped a healthy node's actors. Time the health loop did not run is
    credited to the nodes; a node that is really gone still dies."""
    import time

    from ray_tpu.core.simcluster import SimCluster

    async def scenario():
        cluster = SimCluster(num_nodes=4, seed=3)
        await cluster.start()
        try:
            assert await cluster.wait_until(
                lambda: cluster.registered_count() == 4, timeout=10)
            verdicts = []
            mark_dead = cluster.gcs._mark_node_dead

            async def spy(node_id):
                verdicts.append(node_id)
                await mark_dead(node_id)

            cluster.gcs._mark_node_dead = spy
            # Heartbeats that arrive while the GCS is frozen sit in its
            # socket until after the health loop's overdue wake-up.
            thawed = asyncio.Event()
            heartbeat = cluster.gcs.handle_heartbeat

            async def queued_heartbeat(*args, **kwargs):
                await thawed.wait()
                return await heartbeat(*args, **kwargs)

            cluster.gcs.handle_heartbeat = queued_heartbeat
            time.sleep(1.0)            # the freeze: 3x the death threshold
            await asyncio.sleep(0.15)  # the health loop runs first
            thawed.set()
            await asyncio.sleep(0.3)
            assert verdicts == []
            assert cluster.registered_count() == 4
            victim = next(iter(cluster.raylets))
            cluster.crash_raylet(victim)
            assert await cluster.wait_until(
                lambda: verdicts == [victim], timeout=10), verdicts
        finally:
            await cluster.stop()

    _run(scenario())


def test_reconstruction_degrades_to_typed_errors():
    """Exhausted budget and disabled retention keep today's typed
    failures: max_retries=0 (or lineage_reconstruction=False) objects
    are final — the borrower's get raises ObjectLostError, never hangs
    and never silently recomputes."""
    from ray_tpu.core.config import ray_config
    from ray_tpu.core.simcluster import SimCluster
    from ray_tpu.exceptions import ObjectLostError

    async def scenario():
        cluster = SimCluster(num_nodes=4, seed=5)
        await cluster.start()
        try:
            assert await cluster.wait_until(
                lambda: cluster.registered_count() == 4, timeout=10)
            drv = cluster.driver
            borrower = cluster.add_driver("borrower")
            # Arm 1: budget 0 -> loss is final.
            frozen = await drv.create_object("frozen", max_retries=0)
            for h in list(drv._objects[frozen]["nodes"]):
                cluster.crash_raylet(h)
            with pytest.raises(ObjectLostError):
                await borrower.get_object(frozen, owner="driver",
                                          timeout=8)
            # Arm 2: flag off -> nothing is retained at all.
            ray_config().apply_system_config(
                {"lineage_reconstruction": False})
            try:
                off = await drv.create_object("off", max_retries=5)
                assert drv.lineage.get(off) is None  # no retention
                for h in list(drv._objects[off]["nodes"]):
                    cluster.crash_raylet(h)
                with pytest.raises(ObjectLostError):
                    await borrower.get_object(off, owner="driver",
                                              timeout=8)
            finally:
                ray_config().apply_system_config(
                    {"lineage_reconstruction": True})
            assert drv.exec_counts == {"frozen": 1, "off": 1}
        finally:
            await cluster.stop()

    _run(scenario())


def test_reconstruction_budget_is_capped_and_spent():
    """The per-object re-execution budget is real: each loss spends one
    re-execution; when it runs out the next loss surfaces
    ObjectLostError. The global lineage_reconstruction_budget caps
    whatever max_retries asked for."""
    from ray_tpu.core.config import ray_config
    from ray_tpu.core.simcluster import SimCluster
    from ray_tpu.exceptions import ObjectLostError

    async def scenario():
        cluster = SimCluster(num_nodes=4, seed=13)
        await cluster.start()
        try:
            assert await cluster.wait_until(
                lambda: cluster.registered_count() == 4, timeout=10)
            drv = cluster.driver
            oid = await drv.create_object("bounded", max_retries=2)
            for round_ in range(2):
                assert cluster.evict_sim_object(oid) >= 1, round_
                assert (await drv.get_object(oid, timeout=20)
                        == "bounded()"), round_
            assert drv.exec_counts["bounded"] == 3  # 1 + 2 re-execs
            assert cluster.evict_sim_object(oid) >= 1
            with pytest.raises(ObjectLostError):
                await drv.get_object(oid, timeout=8)
            # The cap clamps extravagant budgets.
            saved = ray_config().lineage_reconstruction_budget
            ray_config().apply_system_config(
                {"lineage_reconstruction_budget": 1})
            try:
                rec = drv.lineage.retain(["simobj-x"], {"name": "x"},
                                         [], 999)
                assert rec["left"] == 1
            finally:
                ray_config().apply_system_config(
                    {"lineage_reconstruction_budget": saved})
        finally:
            await cluster.stop()

    _run(scenario())


# ---------------------------------------------------------------------------
# THE acceptance scenario
# ---------------------------------------------------------------------------

def _acceptance_run(tmp_path, run_idx):
    """100 nodes; seeded schedule = GCS kill -9 mid-run + 10% raylet
    crashes + 1% message drops; workload = tasks + placement groups.
    Returns (completed, lost, leak, violations, registered, schedule)."""
    from ray_tpu.core.faults import FaultPlan
    from ray_tpu.core.simcluster import SimCluster

    SEED = 1914
    N = 100

    async def scenario():
        path = os.path.join(tmp_path, f"gcs-{run_idx}.pkl")
        plan = FaultPlan(seed=SEED)
        plan.drop(p=0.01)                      # 1% drops, every edge
        rng_victims = [f"simnode{i:04d}" for i in
                       __import__("random").Random(SEED).sample(
                           range(N), 10)]      # 10% of the fleet
        cluster = SimCluster(num_nodes=N, seed=SEED, storage_path=path,
                             plan=plan)
        await cluster.start()
        try:
            assert await cluster.wait_until(
                lambda: cluster.registered_count() == N, timeout=20)
            await asyncio.sleep(1.2)  # persist the membership table

            async def tasks():
                return await asyncio.gather(
                    *(cluster.driver.submit_task(hold_s=0.005)
                      for _ in range(300)))

            async def pgs():
                out = []
                for _ in range(6):
                    out.append(await cluster.driver
                               .create_placement_group([{"CPU": 1.0}] * 4))
                return out

            t_work = asyncio.ensure_future(tasks())
            t_pgs = asyncio.ensure_future(pgs())
            await asyncio.sleep(0.3)
            # The seeded chaos: kill the control plane, crash 10 nodes.
            cluster.kill_gcs()
            for v in rng_victims:
                cluster.crash_raylet(v)
            await asyncio.sleep(0.6)
            await cluster.restart_gcs()

            results = await t_work
            created = await t_pgs
            # zero lost tasks
            assert all(results), f"{results.count(False)} tasks lost"
            assert not cluster.driver.lost
            # full re-registration: every survivor is alive in the
            # recovered table, every victim is declared dead
            assert await cluster.wait_until(
                lambda: cluster.registered_count() == N - 10, timeout=20)
            # groups terminated cleanly; remove them all, then zero
            # leaked reservations cluster-wide
            for pg_id, state in created:
                assert state in ("CREATED", "INFEASIBLE"), state
                await cluster.driver.remove_placement_group(pg_id)
            assert await cluster.wait_until(
                lambda: not cluster.leaked_reservations()
                and not cluster.resource_violations(), timeout=15), (
                cluster.leaked_reservations(),
                cluster.resource_violations())
            # The replayable schedule: pure per-edge previews.
            schedule = plan.preview("driver", "simnode0001",
                                    "request_sim_lease", 200)
            return (len(cluster.driver.completed),
                    [x.key() for x in schedule])
        finally:
            await cluster.stop()

    return _run(scenario(), timeout=180)


def test_acceptance_100_nodes_survive_seeded_fault_schedule(tmp_path):
    completed_a, schedule_a = _acceptance_run(tmp_path, 0)
    assert completed_a == 300
    # Re-running the same seed reproduces the identical fault schedule.
    completed_b, schedule_b = _acceptance_run(tmp_path, 1)
    assert completed_b == 300
    assert schedule_a == schedule_b


def _data_plane_acceptance_run(run_idx):
    """Round-15 acceptance: mid-run, kill the node holding a borrowed
    object AND a placement-group member node, under 1% seeded drops.
    The borrower's in-flight get() must return the reconstructed value
    (no user-visible error), the PG must return to CREATED on the
    survivors, and nothing may leak. Returns the observables a seed
    replay must reproduce exactly."""
    from ray_tpu.core.faults import FaultPlan
    from ray_tpu.core.simcluster import SimCluster

    SEED = 1915

    async def scenario():
        plan = FaultPlan(seed=SEED)
        plan.drop(p=0.01)
        cluster = SimCluster(num_nodes=12, seed=SEED, plan=plan)
        await cluster.start()
        try:
            assert await cluster.wait_until(
                lambda: cluster.registered_count() == 12, timeout=15)
            drv = cluster.driver
            borrower = cluster.add_driver("borrower")
            base = await drv.create_object("base")
            mid = await drv.create_object("mid", deps=[base])
            assert (await borrower.get_object(mid, owner="driver")
                    == "mid(base())")
            pg_id, state = await cluster.driver.create_placement_group(
                [{"CPU": 1.0}] * 3, strategy="STRICT_SPREAD")
            assert state == "CREATED"
            info = await drv._gcs.get_placement_group(pg_id)
            pg_victim = info["bundle_locations"][0]["node_id"]

            # Mid-run: the borrower has a get in flight while the node
            # holding its borrowed object, both producers' stores, and
            # a PG member all die.
            get_inflight = asyncio.ensure_future(
                borrower.get_object(mid, owner="driver", timeout=30))
            await asyncio.sleep(0.01)
            victims = ({pg_victim, borrower.node, drv.node}
                       | set(drv._objects[base]["nodes"])
                       | set(drv._objects[mid]["nodes"])) - {None}
            for v in victims:
                cluster.crash_raylet(v)

            # The in-flight get lands the correct value whether it beat
            # the crash (cached copy) or blocked-and-retried through
            # the re-execution — never a user-visible error.
            assert await get_inflight == "mid(base())"
            # A post-crash get from the re-homed borrower cannot be
            # served by any surviving copy: it MUST reconstruct.
            value = await borrower.get_object(mid, owner="driver",
                                              timeout=30)
            assert value == "mid(base())", value
            assert drv.lineage.stats()["reexecs"] >= 1
            assert drv.exec_counts["mid"] >= 2

            def pg_recovered():
                pg = cluster.gcs.placement_groups.get(pg_id) or {}
                locs = [loc["node_id"]
                        for loc in pg.get("bundle_locations") or []]
                return (pg.get("state") == "CREATED" and locs
                        and all(cluster.raylets[n].alive for n in locs))

            assert await cluster.wait_until(pg_recovered, timeout=20), (
                cluster.gcs.placement_groups.get(pg_id))
            assert await cluster.wait_until(
                lambda: not cluster.leaked_reservations()
                and not cluster.resource_violations(), timeout=15), (
                cluster.leaked_reservations(),
                cluster.resource_violations())
            pg = cluster.gcs.placement_groups[pg_id]
            schedule = plan.preview("borrower", "simnode0000",
                                    "pull_sim_object", 50)
            return (value, pg["state"], len(cluster.leaked_reservations()),
                    [x.key() for x in schedule])
        finally:
            await cluster.stop()

    return _run(scenario(), timeout=120)


def test_acceptance_data_plane_recovery_and_seed_replay():
    value_a, pg_state_a, leaks_a, sched_a = _data_plane_acceptance_run(0)
    assert (value_a, pg_state_a, leaks_a) == ("mid(base())", "CREATED", 0)
    # Identical outcome on seed replay: same reconstructed value, same
    # recovered PG state, zero leaks both times, identical fault
    # schedule.
    value_b, pg_state_b, leaks_b, sched_b = _data_plane_acceptance_run(1)
    assert (value_a, pg_state_a, leaks_a, sched_a) == (
        value_b, pg_state_b, leaks_b, sched_b)


# ---------------------------------------------------------------------------
# scale: 1000 simulated nodes (ROADMAP 3d)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_1000_nodes_register_heartbeat_and_lease():
    """The sim harness holds at 1000 in-process raylets: full
    registration, a lease sweep through the real spillback policy, and
    a placement round — the GCS dispatch profile at this scale is
    recorded in PROFILE.md (round 11). Kept `-m slow`: ~1-2 min on a
    2-CPU box, dominated by 1000 heartbeat loops."""
    from ray_tpu.core.simcluster import SimCluster

    async def scenario():
        # Timers scale with N (PROFILE round 11): at the default sim
        # compression, 1000 heartbeat loops plus full-table view
        # refreshes saturate the loop, heartbeats fall behind the
        # 1.5 s health deadline, and the false-death/re-register storm
        # never converges. A real 1000-node deployment scales these
        # the same way.
        cluster = SimCluster(num_nodes=1000, seed=41, config={
            "raylet_heartbeat_period_ms": 1000,
            "cluster_view_refresh_ms": 10000,
            "health_check_period_ms": 2000,
            "health_check_failure_threshold": 10,
        })
        await cluster.start()
        try:
            assert await cluster.wait_until(
                lambda: cluster.registered_count() == 1000, timeout=120)
            results = await asyncio.gather(
                *(cluster.driver.submit_task() for _ in range(300)))
            assert all(results)
            assert not cluster.driver.lost
            pg_id, state = await cluster.driver.create_placement_group(
                [{"CPU": 1.0}] * 8, strategy="SPREAD")
            assert state == "CREATED"
            await cluster.driver.remove_placement_group(pg_id)
            assert await cluster.wait_until(
                lambda: not cluster.leaked_reservations(), timeout=30)
        finally:
            await cluster.stop()

    _run(scenario(), timeout=600)
