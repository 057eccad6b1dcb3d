"""Multi-node-on-one-machine test harness.

Reference equivalent: `python/ray/cluster_utils.py:108` (`Cluster`,
`add_node :174`) — additional raylets run as local processes sharing one
GCS, giving a real N-node cluster on a single machine (the key trick for
multi-host tests without hardware, SURVEY.md §4.2).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict, List, Optional

from ray_tpu.core import procs
from ray_tpu.core.ids import NodeID
from ray_tpu.core.node import NodeSupervisor, detect_node_resources


class Cluster:
    def __init__(self, initialize_head: bool = True,
                 head_node_args: Optional[dict] = None):
        self._supervisor: Optional[NodeSupervisor] = None
        self._extra_raylets: List[subprocess.Popen] = []
        self.gcs_address: Optional[str] = None
        self.head_raylet_address: Optional[str] = None
        if initialize_head:
            args = head_node_args or {}
            self._supervisor = NodeSupervisor.start_head(
                num_cpus=args.get("num_cpus", 2),
                resources=args.get("resources"),
                object_store_memory=args.get("object_store_memory"))
            self.gcs_address = self._supervisor.gcs_address
            self.head_raylet_address = self._supervisor.raylet_address

    @property
    def address(self) -> str:
        return self.gcs_address

    def add_node(self, num_cpus: int = 2,
                 resources: Optional[Dict[str, float]] = None,
                 env: Optional[Dict[str, str]] = None,
                 object_store_memory: Optional[int] = None) -> dict:
        """Start another raylet against the shared GCS."""
        node_id = NodeID.from_random().hex()
        all_resources = detect_node_resources(num_cpus=num_cpus)
        # detect_node_resources pulls host CPU count; pin what was asked.
        all_resources["CPU"] = float(num_cpus)
        all_resources.update(resources or {})
        cmd = [sys.executable, "-m", "ray_tpu.core.raylet",
               "--gcs", self.gcs_address, "--node-id", node_id,
               "--resources", json.dumps(all_resources)]
        if object_store_memory:
            cmd += ["--object-store-memory", str(object_store_memory)]
        child_env = dict(os.environ)
        if self._supervisor is not None:
            child_env["RAY_TPU_LOG_DIR"] = self._supervisor.log_dir
        child_env.update(env or {})
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                stderr=subprocess.DEVNULL, env=child_env)
        from ray_tpu.core.node import _wait_for_line
        address = _wait_for_line(proc, r"RAYLET_ADDRESS=(\S+)")
        self._extra_raylets.append(proc)
        return {"node_id": node_id, "address": address, "proc": proc}

    def kill_node(self, node: dict) -> None:
        """Fault injection: hard-kill a raylet (reference:
        _private/test_utils.py NodeKillerActor)."""
        node["proc"].kill()
        node["proc"].wait()

    def wait_for_nodes(self, count: int, timeout: float = 20.0) -> None:
        import ray_tpu
        deadline = time.time() + timeout
        while time.time() < deadline:
            alive = [n for n in ray_tpu.nodes() if n["Alive"]]
            if len(alive) >= count:
                return
            time.sleep(0.2)
        raise TimeoutError(f"cluster did not reach {count} nodes")

    def shutdown(self) -> None:
        procs.end_processes(self._extra_raylets,
                            grace_s=procs.RAYLET_GRACE_S)
        self._extra_raylets.clear()
        if self._supervisor is not None:
            self._supervisor.stop()
