"""Node providers: the boundary between the autoscaler and machines.

Reference equivalent: `python/ray/autoscaler/node_provider.py` (the v1
NodeProvider interface) + `_private/fake_multi_node/node_provider.py`
(the in-process provider used by autoscaler tests). A provider knows how
to create/terminate nodes of a given type and report what exists; the
autoscaler never touches machines directly.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from ray_tpu.core import procs


@dataclass
class NodeType:
    """A launchable shape (reference: available_node_types entries)."""

    name: str
    resources: Dict[str, float]
    min_workers: int = 0
    max_workers: int = 10


class NodeProvider:
    """Interface. Implementations: LocalNodeProvider (raylet processes on
    this host); cloud/TPU-pod providers plug in the same way the
    reference's AWS/GCP/KubeRay providers do."""

    def create_node(self, node_type: NodeType) -> str:
        raise NotImplementedError

    def terminate_node(self, node_id: str) -> None:
        raise NotImplementedError

    def non_terminated_nodes(self) -> List[str]:
        raise NotImplementedError


@dataclass
class _LocalNode:
    node_id: str
    proc: subprocess.Popen
    node_type: str


class LocalNodeProvider(NodeProvider):
    """Spawns extra raylets against an existing GCS — one process per
    'node' (reference: fake multinode docker-less mode)."""

    def __init__(self, gcs_address: str,
                 env: Optional[Dict[str, str]] = None):
        self.gcs_address = gcs_address
        self._env = env or {}
        self._nodes: Dict[str, _LocalNode] = {}

    def create_node(self, node_type: NodeType) -> str:
        from ray_tpu.core.ids import NodeID
        from ray_tpu.core.node import _wait_for_line

        node_id = NodeID.from_random().hex()
        cmd = [sys.executable, "-m", "ray_tpu.core.raylet",
               "--gcs", self.gcs_address, "--node-id", node_id,
               "--resources", json.dumps(node_type.resources)]
        env = dict(os.environ)
        env.update(self._env)
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                stderr=subprocess.DEVNULL, env=env)
        _wait_for_line(proc, r"RAYLET_ADDRESS=(\S+)")
        self._nodes[node_id] = _LocalNode(node_id, proc, node_type.name)
        return node_id

    def terminate_node(self, node_id: str) -> None:
        node = self._nodes.pop(node_id, None)
        if node is None:
            return
        procs.end_processes([node.proc], grace_s=procs.RAYLET_GRACE_S)

    def non_terminated_nodes(self) -> List[str]:
        return [nid for nid, n in self._nodes.items()
                if n.proc.poll() is None]
