"""ServeController: the reconciliation loop.

Reference equivalent: `python/ray/serve/_private/controller.py:87,347` —
an actor holding target state (deployments, versions, replica counts) and
converging actual state to it: starting replicas, draining and stopping
extras, rolling version updates one replica at a time (start-new →
drain-old), restarting dead replicas, and queue-length autoscaling
(`autoscaling_policy.py:12`).
"""

from __future__ import annotations

import asyncio
import math
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

CONTROLLER_NAME = "SERVE_CONTROLLER"


@dataclass
class _ReplicaState:
    handle: Any
    replica_id: str
    version: Optional[str]
    state: str = "STARTING"        # STARTING | RUNNING | STOPPING
    ongoing: int = 0
    last_seen: float = field(default_factory=time.monotonic)


@dataclass
class _DeploymentState:
    name: str
    cls_factory: Any
    init_args: tuple
    init_kwargs: dict
    config: Any                    # DeploymentConfig
    target_replicas: int
    replicas: List[_ReplicaState] = field(default_factory=list)
    route_version: int = 0         # bumped whenever the running set changes
    last_scale_up: float = 0.0
    last_scale_down: float = 0.0
    _scale_high_since: Optional[float] = None
    _scale_low_since: Optional[float] = None


CHECKPOINT_KEY = "serve:controller_ckpt"


class ServeController:
    def __init__(self):
        self._deployments: Dict[str, _DeploymentState] = {}
        self._routes: Dict[str, str] = {}   # route_prefix -> deployment
        self._routes_version = 0
        self._shutdown = False
        # The ctor runs off the actor event loop; the reconcile task is
        # created lazily from the first async call, which does run on it.
        self._loop_task = None
        self._ckpt_fingerprint: Any = None
        # Crash recovery (reference: controller.py:87 — state is
        # checkpointed to GCS KV and reloaded on restart; replicas are
        # detached named actors that the new incarnation re-adopts).
        try:
            self._recover()
        except Exception:
            import traceback

            traceback.print_exc()

    def _ensure_reconciler(self) -> None:
        if self._loop_task is None or self._loop_task.done():
            self._loop_task = asyncio.get_running_loop().create_task(
                self._reconcile_loop())

    # -- durability -----------------------------------------------------
    @staticmethod
    def _kv_put(key: str, blob: bytes) -> None:
        from ray_tpu.core.worker import current_runtime

        rt = current_runtime()
        rt._loop.run(rt._gcs.kv_put(key, blob, True), timeout=10)

    @staticmethod
    def _kv_get(key: str):
        from ray_tpu.core.worker import current_runtime

        rt = current_runtime()
        return rt._loop.run(rt._gcs.kv_get(key), timeout=10)

    def _fingerprint(self):
        return (
            self._routes_version,
            tuple(sorted(
                (n, st.target_replicas, st.route_version,
                 tuple(sorted((r.replica_id, r.state)
                              for r in st.replicas)))
                for n, st in self._deployments.items())),
        )

    def _save_checkpoint(self) -> None:
        """Persist target state + the live replica set to GCS KV on
        every mutation; cheap no-op when nothing changed."""
        fp = self._fingerprint()
        if fp == self._ckpt_fingerprint:
            return
        import cloudpickle

        blob = cloudpickle.dumps({
            "routes": dict(self._routes),
            "routes_version": self._routes_version,
            "deployments": {
                name: {
                    "cls_factory": st.cls_factory,
                    "init_args": st.init_args,
                    "init_kwargs": st.init_kwargs,
                    "config": st.config,
                    "target_replicas": st.target_replicas,
                    # route_version must survive restarts: listeners
                    # hold the old incarnation's counters, and a reset
                    # counter would never exceed them — their long-polls
                    # would go silent forever.
                    "route_version": st.route_version,
                    "replicas": [(r.replica_id, r.version, r.state)
                                 for r in st.replicas],
                } for name, st in self._deployments.items()},
        })
        try:
            self._kv_put(CHECKPOINT_KEY, blob)
            self._ckpt_fingerprint = fp
        except Exception:
            import traceback

            traceback.print_exc()

    def _recover(self) -> None:
        import cloudpickle

        blob = self._kv_get(CHECKPOINT_KEY)
        if not blob:
            return
        import ray_tpu

        data = cloudpickle.loads(blob)
        self._routes = dict(data.get("routes", {}))
        self._routes_version = data.get("routes_version", 0) + 1
        for name, d in data.get("deployments", {}).items():
            st = _DeploymentState(
                name=name, cls_factory=d["cls_factory"],
                init_args=tuple(d["init_args"]),
                init_kwargs=dict(d["init_kwargs"]),
                config=d["config"],
                target_replicas=d["target_replicas"])
            for rid, version, rstate in d.get("replicas", ()):
                if rstate != "RUNNING":
                    continue  # half-started replicas restart fresh
                try:
                    handle = ray_tpu.get_actor(f"SERVE_REPLICA::{rid}")
                except Exception:
                    continue  # died with the old controller's node
                st.replicas.append(_ReplicaState(
                    handle=handle, replica_id=rid, version=version,
                    state="RUNNING"))
            st.route_version = d.get("route_version", 0) + 1
            self._deployments[name] = st

    # -- API (driver / serve.run) --------------------------------------
    async def deploy(self, name: str, cls_factory, init_args, init_kwargs,
                     config, route_prefix: Optional[str] = None) -> bool:
        """Create or update a deployment. A changed version triggers a
        rolling update; a changed num_replicas scales."""
        self._ensure_reconciler()
        if config.version is None:
            # Auto-version from the code + constructor args so an
            # unversioned redeploy with changes still rolls (reference:
            # serve computes a config/code version hash when the user
            # does not pin one).
            import hashlib

            import cloudpickle

            try:
                blob = cloudpickle.dumps(
                    (cls_factory, init_args, init_kwargs))
                config.version = hashlib.sha1(blob).hexdigest()[:12]
            except Exception:
                pass  # unpicklable corner: keep None (no auto-roll)
        existing = self._deployments.get(name)
        target = (config.autoscaling_config.min_replicas
                  if config.autoscaling_config else config.num_replicas)
        if existing is None:
            self._deployments[name] = _DeploymentState(
                name=name, cls_factory=cls_factory,
                init_args=tuple(init_args), init_kwargs=dict(init_kwargs),
                config=config, target_replicas=target)
        else:
            existing.cls_factory = cls_factory
            existing.init_args = tuple(init_args)
            existing.init_kwargs = dict(init_kwargs)
            old_autoscaling = existing.config.autoscaling_config
            existing.config = config
            if config.autoscaling_config is None:
                existing.target_replicas = config.num_replicas
            elif old_autoscaling is None:
                existing.target_replicas = target
        if route_prefix is not None and \
                self._routes.get(route_prefix) != name:
            self._routes[route_prefix] = name
            self._routes_version += 1
        self._save_checkpoint()
        return True

    async def delete_deployment(self, name: str) -> bool:
        state = self._deployments.pop(name, None)
        if state is None:
            return False
        if any(d == name for d in self._routes.values()):
            self._routes = {r: d for r, d in self._routes.items()
                            if d != name}
            self._routes_version += 1
        await asyncio.gather(
            *[self._stop_replica(state, r) for r in list(state.replicas)],
            return_exceptions=True)
        self._save_checkpoint()
        return True

    async def get_routing_table(self, name: str) -> Dict[str, Any]:
        """Running replicas for a deployment + a version counter the
        router uses for cache invalidation."""
        self._ensure_reconciler()
        state = self._deployments.get(name)
        if state is None:
            return {"version": -1, "replicas": []}
        return {
            "version": state.route_version,
            "replicas": [(r.replica_id, r.handle) for r in state.replicas
                         if r.state == "RUNNING"],
        }

    async def get_routes(self) -> Dict[str, str]:
        return dict(self._routes)

    async def listen_for_change(self, versions: Dict[str, int],
                                timeout_s: float = 10.0) -> Dict[str, Any]:
        """Long-poll (reference: long_poll.py:174 LongPollHost): blocks
        until the route table or any listed deployment's routing version
        moves past the caller's snapshot, or timeout_s elapses; returns
        the changed snapshots. `versions` maps "__routes__" and
        deployment names to the caller's last-seen versions; a caller
        hears only of the keys it listed (a router lists its deployment
        and not "__routes__": told of the route table, which it cannot
        acknowledge, its poll would return at once, every time)."""
        self._ensure_reconciler()
        loop = asyncio.get_running_loop()
        deadline = loop.time() + timeout_s

        def changed() -> Dict[str, Any]:
            out: Dict[str, Any] = {}
            if self._routes_version > versions.get(
                    "__routes__", self._routes_version):
                out["__routes__"] = {"version": self._routes_version,
                                     "routes": dict(self._routes)}
            for name, seen in versions.items():
                if name == "__routes__":
                    continue
                st = self._deployments.get(name)
                if st is None:
                    if seen != -1:
                        # Deleted: tell the listener to STOP polling —
                        # otherwise dead-deployment pollers pile up and
                        # exhaust the controller's concurrency slots.
                        out[name] = {"version": -1, "replicas": [],
                                     "deleted": True}
                    continue
                if st.route_version > seen:
                    out[name] = {
                        "version": st.route_version,
                        "replicas": [(r.replica_id, r.handle)
                                     for r in st.replicas
                                     if r.state == "RUNNING"],
                    }
            return out

        while True:
            out = changed()
            if out or loop.time() >= deadline or self._shutdown:
                return out
            await asyncio.sleep(0.05)

    async def status(self) -> Dict[str, Any]:
        out = {}
        for name, st in self._deployments.items():
            out[name] = {
                "target_replicas": st.target_replicas,
                "replicas": [
                    {"id": r.replica_id, "state": r.state,
                     "version": r.version, "ongoing": r.ongoing}
                    for r in st.replicas],
            }
        return out

    async def shutdown(self) -> bool:
        self._shutdown = True
        for state in list(self._deployments.values()):
            await self.delete_deployment(state.name)
        # A later serve instance must start empty, not adopt this one.
        try:
            import cloudpickle

            self._kv_put(CHECKPOINT_KEY, cloudpickle.dumps({}))
        except Exception:
            pass
        return True

    # -- reconciliation -------------------------------------------------
    async def _reconcile_loop(self) -> None:
        while not self._shutdown:
            try:
                for state in list(self._deployments.values()):
                    await self._reconcile(state)
                    await self._autoscale(state)
                # Replica-set / autoscale changes persist too, so a
                # restarted controller re-adopts the same live actors.
                self._save_checkpoint()
            except Exception:
                import traceback

                traceback.print_exc()
            await asyncio.sleep(0.1)

    async def _reconcile(self, state: _DeploymentState) -> None:
        version = state.config.version
        # 1. Reap dead replicas (health probe).
        for r in list(state.replicas):
            if r.state != "RUNNING":
                continue
            if time.monotonic() - r.last_seen \
                    < state.config.health_check_period_s:
                continue
            try:
                await _aget(r.handle.check_health.remote(), timeout=5.0)
                r.last_seen = time.monotonic()
            except Exception:
                # Reap AND kill: dropping it from the table without
                # killing would leak a live actor (and its resources)
                # serving stale traffic forever.
                state.replicas.remove(r)
                state.route_version += 1
                try:
                    import ray_tpu

                    ray_tpu.kill(r.handle)
                except Exception:
                    pass
        running = [r for r in state.replicas if r.state == "RUNNING"]
        current = [r for r in running if r.version == version]
        outdated = [r for r in running if r.version != version]
        starting = [r for r in state.replicas if r.state == "STARTING"]

        # 2. Scale up: missing replicas (count outdated ones still serving
        # so a rolling update replaces one at a time instead of doubling).
        deficit = state.target_replicas - (len(current) + len(starting)
                                           + len(outdated))
        # During a rolling update keep one extra slot so a new-version
        # replica starts BEFORE an old one drains (no capacity dip).
        if outdated and deficit <= 0:
            deficit = 1 if not starting else 0
        for _ in range(max(deficit, 0)):
            try:
                # Actor creation returns once the constructor has run,
                # and a replica that loads a model takes a while: off
                # the loop, so status() and routing stay answerable.
                await asyncio.to_thread(self._start_replica, state)
            except Exception:
                # Constructor failed synchronously (user __init__ error):
                # back off one tick instead of crash-looping hot.
                import traceback

                traceback.print_exc()
                break

        # 3. Rolling replace: once a current-version replica is up, drain
        # outdated ones.
        surplus = (len(current) + len(outdated)) - state.target_replicas
        if outdated and len(current) >= 1 and surplus > 0:
            await self._stop_replica(state, outdated[0])

        # 4. Scale down extras of the current version.
        elif len(current) > state.target_replicas:
            victim = min(current, key=lambda r: r.ongoing)
            await self._stop_replica(state, victim)

        # 5. Promote replicas that finished starting; drop ones whose
        # actor died during __init__ (or never came up) so the deficit
        # recomputes and a replacement starts — otherwise a ghost
        # STARTING entry wedges the deployment at 0 RUNNING forever.
        from ray_tpu.exceptions import RayActorError

        for r in starting:
            try:
                await _aget(r.handle.check_health.remote(), timeout=0.5)
            except RayActorError:
                state.replicas.remove(r)
                continue
            except Exception:
                if time.monotonic() - r.last_seen > 120.0:
                    state.replicas.remove(r)
                    try:
                        import ray_tpu

                        ray_tpu.kill(r.handle)
                    except Exception:
                        pass
                continue
            r.state = "RUNNING"
            r.last_seen = time.monotonic()
            state.route_version += 1

    def _start_replica(self, state: _DeploymentState) -> None:
        import ray_tpu
        from ray_tpu.serve._private.replica import Replica

        replica_id = f"{state.name}#{uuid.uuid4().hex[:6]}"
        opts = dict(state.config.ray_actor_options)
        opts.setdefault("num_cpus", 0)
        opts.setdefault("max_concurrency",
                        state.config.max_ongoing_requests)
        # Detached + named: replicas survive a controller crash and the
        # restarted controller re-adopts them by name (reference:
        # deployment_state.py ActorReplicaWrapper named actors).
        opts.setdefault("name", f"SERVE_REPLICA::{replica_id}")
        opts.setdefault("lifetime", "detached")
        actor_cls = ray_tpu.remote(**opts)(Replica)
        handle = actor_cls.remote(
            state.cls_factory, state.init_args, state.init_kwargs,
            state.name, replica_id, state.config.version)
        state.replicas.append(_ReplicaState(
            handle=handle, replica_id=replica_id,
            version=state.config.version))

    async def _stop_replica(self, state: _DeploymentState,
                            replica: _ReplicaState) -> None:
        import ray_tpu

        if replica in state.replicas:
            replica.state = "STOPPING"
            state.replicas.remove(replica)
            state.route_version += 1
        try:
            await _aget(
                replica.handle.prepare_for_shutdown.remote(
                    state.config.graceful_shutdown_timeout_s),
                timeout=state.config.graceful_shutdown_timeout_s + 5)
        except Exception:
            pass
        try:
            ray_tpu.kill(replica.handle)
        except Exception:
            pass

    # -- autoscaling ----------------------------------------------------
    async def _collect_metric_snapshots(self) -> list:
        """Every process's pushed app-metric snapshot: the local registry
        (covers local mode, where proxies/routers/replicas share this
        process) plus the cluster-wide view.

        Round 17: the cluster half reads the GCS's latest pipeline fold
        — ONE RPC instead of a get_metrics poll per raylet per
        autoscale tick (the bespoke poll path this satellite deletes).
        `metrics_poll_fallback` restores the old fan-out for one
        release; an empty fold (pipeline warming up) also falls back."""
        from ray_tpu.util.metrics import default_registry

        snaps = list(default_registry().snapshot())
        from ray_tpu.core import metrics_ts
        from ray_tpu.core.config import ray_config
        from ray_tpu.core.worker import current_runtime

        rt = current_runtime()
        if getattr(rt, "is_local_mode", False):
            return snaps
        cfg = ray_config()
        if (metrics_ts.enabled and cfg.metrics_pipeline
                and not cfg.metrics_poll_fallback):
            try:
                fold = await rt._gcs.latest_metrics()
                if fold:
                    snaps.extend(fold)
                    return snaps
            except Exception:
                pass  # fold unavailable — fall through to the poll
        try:
            for n in await rt._gcs.get_nodes():
                if not n.get("alive"):
                    continue
                try:
                    client = await rt._raylet_client(n["address"])
                    snaps.extend(await client.call("get_metrics",
                                                   timeout=5.0))
                except Exception:
                    continue
        except Exception:
            pass
        return snaps

    async def _autoscale(self, state: _DeploymentState) -> None:
        """Queue-length autoscaling driven by the data plane's OWN
        gauges — `serve_replica_ongoing_requests` (per live replica) +
        `serve_deployment_queued_queries` (per router process backlog) —
        instead of an extra metrics.remote() poll per replica per tick
        (the PR-2 follow-up in ROADMAP). The gauges lag by the metrics
        push interval; upscale/downscale delays already absorb that. If
        no gauge has been pushed yet for any live replica (fresh
        deployment), fall back to one polling round."""
        cfg = state.config.autoscaling_config
        if cfg is None:
            return
        running = [r for r in state.replicas if r.state == "RUNNING"]
        if not running:
            return
        try:
            snaps = await self._collect_metric_snapshots()
        except Exception:
            snaps = []
        per_replica, queued = _deployment_load_from_samples(
            snaps, state.name, [r.replica_id for r in running])
        if per_replica:
            total = queued
            for r in running:
                if r.replica_id in per_replica:
                    r.ongoing = int(per_replica[r.replica_id])
                total += per_replica.get(r.replica_id, 0)
        else:
            total = 0
            for r in running:
                try:
                    m = await _aget(r.handle.metrics.remote(), timeout=2.0)
                    r.ongoing = m["ongoing"]
                    total += m["ongoing"]
                except Exception:
                    pass
        desired = math.ceil(total / max(cfg.target_ongoing_requests, 1e-9))
        desired = min(max(desired, cfg.min_replicas), cfg.max_replicas)
        now = time.monotonic()
        if desired > state.target_replicas:
            state._scale_low_since = None
            if state._scale_high_since is None:
                state._scale_high_since = now
            if now - state._scale_high_since >= cfg.upscale_delay_s:
                state.target_replicas = desired
                state._scale_high_since = None
        elif desired < state.target_replicas:
            state._scale_high_since = None
            if state._scale_low_since is None:
                state._scale_low_since = now
            if now - state._scale_low_since >= cfg.downscale_delay_s:
                state.target_replicas = desired
                state._scale_low_since = None
        else:
            state._scale_high_since = None
            state._scale_low_since = None


def _deployment_load_from_samples(snapshots: list, deployment: str,
                                  live_replica_ids: list):
    """Fold metric snapshots into autoscaling inputs for one deployment.

    Returns `(per_replica_ongoing, queued_total)`:
    - `per_replica_ongoing`: replica_id -> latest
      `serve_replica_ongoing_requests` gauge value, restricted to the
      LIVE replica set (dead replicas' gauges linger in raylet snapshots
      until worker eviction and must not count);
    - `queued_total`: sum of `serve_deployment_queued_queries` across
      router processes (each process aggregates its own backlog, so the
      cluster total is the sum over sources).
    """
    live = set(live_replica_ids)
    per_replica: Dict[str, float] = {}
    queued = 0.0
    for m in snapshots:
        if m.get("name") == "serve_replica_ongoing_requests":
            for s in m.get("samples", []):
                tags = s.get("tags", {})
                rid = tags.get("replica")
                if tags.get("deployment") == deployment and rid in live:
                    per_replica[rid] = s.get("value", 0.0)
        elif m.get("name") == "serve_deployment_queued_queries":
            for s in m.get("samples", []):
                if s.get("tags", {}).get("deployment") == deployment:
                    queued += s.get("value", 0.0)
    return per_replica, queued


async def _aget(ref, timeout: Optional[float] = None):
    """Await an ObjectRef from inside the controller's event loop without
    blocking it (ray_tpu.get is thread-blocking)."""
    import ray_tpu

    return await asyncio.to_thread(ray_tpu.get, ref, timeout=timeout)
