"""GCP TPU-pod node provider: slices as atomic autoscaling units.

Reference equivalent: `python/ray/autoscaler/_private/gcp/node_provider.py`
(+ TPU handling in `gcp/config.py`). The cloud surface here is a narrow
protocol modeled on the TPU-VM *queued resources* API
(create/get/delete/list); production implements `GcpTpuApi` with real HTTP
calls, tests use `FakeGcpTpuApi`, which either just records state or spawns
one local raylet per slice host — the fake-multinode strategy of
`autoscaler/_private/fake_multi_node/node_provider.py`.

The key departure from generic cloud providers: **a TPU slice is atomic**.
`create_node` provisions every host of the slice in one call, and
`terminate_node` returns them all — a v5e pod cannot grow or shrink by
single hosts. The autoscaler bin-packs demand against the slice's
*aggregate* resources, so eight `{"TPU": 4}` gang members launch exactly
one v5litepod-32 (8 hosts x 4 chips), never eight separate machines.
"""

from __future__ import annotations

import json
import logging
import os
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from ray_tpu.autoscaler.node_provider import NodeProvider, NodeType
from ray_tpu.core import procs

logger = logging.getLogger(__name__)

# chips per host by TPU generation (reference: accelerators/tpu.py
# chips-per-host bounds; v5e/v5p/v4 pods pack 4 chips per host VM,
# v2/v3 pack 8 tensorcores = 4 chips).
_CHIPS_PER_HOST = {
    "v2": 4, "v3": 4, "v4": 4, "v5litepod": 4, "v5e": 4, "v5p": 4,
    "v6e": 4,
}


def slice_shape(accelerator_type: str) -> Tuple[int, int]:
    """(num_hosts, chips_per_host) for an accelerator type string.

    "v5litepod-32" -> (8, 4); "v5litepod-4" -> (1, 4);
    "v4-16" -> (2, 4) (v4 counts tensorcores: 16 cores = 8 chips).
    """
    gen, _, count_s = accelerator_type.rpartition("-")
    count = int(count_s)
    per_host = _CHIPS_PER_HOST.get(gen, 4)
    # v2-v4 names count tensorcores (2 per chip); v5e+ count chips.
    chips = count // 2 if gen in ("v2", "v3", "v4") else count
    hosts = max(1, chips // per_host)
    return hosts, min(chips, per_host)


@dataclass
class TpuSliceNodeType(NodeType):
    """A launchable slice shape. `resources` is the slice AGGREGATE
    (whole-gang bin-packing); per-host resources derive from the shape."""

    accelerator_type: str = "v5litepod-4"
    runtime_version: str = "v2-alpha-tpuv5-lite"
    cpus_per_host: float = 4.0

    def __post_init__(self):
        hosts, per_host = slice_shape(self.accelerator_type)
        self.num_hosts = hosts
        self.chips_per_host = per_host
        if not self.resources:
            self.resources = {
                "TPU": float(hosts * per_host),
                f"TPU-{self.accelerator_type}": float(hosts * per_host),
                "CPU": self.cpus_per_host * hosts,
            }

    def host_resources(self) -> Dict[str, float]:
        return {
            "TPU": float(self.chips_per_host),
            f"TPU-{self.accelerator_type}": float(self.chips_per_host),
            "CPU": self.cpus_per_host,
        }


class GcpTpuApi:
    """Queued-resources-shaped API surface (the subset the provider
    needs). Real implementation: POST/GET/DELETE against
    tpu.googleapis.com/v2/.../queuedResources."""

    def create_slice(self, name: str, node_type: TpuSliceNodeType) -> dict:
        raise NotImplementedError

    def get_slice(self, name: str) -> Optional[dict]:
        raise NotImplementedError

    def delete_slice(self, name: str) -> None:
        raise NotImplementedError

    def list_slices(self) -> List[dict]:
        raise NotImplementedError


@dataclass
class _FakeSlice:
    name: str
    node_type: TpuSliceNodeType
    state: str = "ACTIVE"
    created_at: float = field(default_factory=time.monotonic)
    procs: List[subprocess.Popen] = field(default_factory=list)
    host_node_ids: List[str] = field(default_factory=list)


class FakeGcpTpuApi(GcpTpuApi):
    """In-memory stub. With `gcs_address` set it also materializes each
    slice host as a local raylet process carrying the host's TPU
    resources and slice labels (RAY_TPU_FAKE_SLICE / TPU_WORKER_ID), so
    autoscaler end-to-end tests exercise real gang scheduling without a
    cloud."""

    def __init__(self, gcs_address: Optional[str] = None):
        self.gcs_address = gcs_address
        self.slices: Dict[str, _FakeSlice] = {}
        self.create_calls = 0
        self._all_procs: List[subprocess.Popen] = []  # lifetime registry

    def create_slice(self, name: str, node_type: TpuSliceNodeType) -> dict:
        if name in self.slices:
            raise ValueError(f"slice {name} already exists")
        self.create_calls += 1
        sl = _FakeSlice(name, node_type, state="PROVISIONING")
        # Register BEFORE the (slow) host bring-up: a real queued-resource
        # exists from the create call onward, and callers must see it —
        # otherwise a second reconcile tick would double-provision.
        self.slices[name] = sl
        if self.gcs_address:
            self._spawn_hosts(sl)
        sl.state = "ACTIVE"
        return {"name": name, "state": sl.state,
                "hosts": sl.host_node_ids or node_type.num_hosts}

    def _spawn_hosts(self, sl: _FakeSlice) -> None:
        from ray_tpu.core.ids import NodeID
        from ray_tpu.core.node import _wait_for_line

        nt = sl.node_type
        for worker_id in range(nt.num_hosts):
            node_id = NodeID.from_random().hex()
            env = dict(os.environ)
            env.update({
                "JAX_PLATFORMS": "cpu",
                "RAY_TPU_FAKE_SLICE":
                    f"{nt.accelerator_type}:{nt.num_hosts}",
                "TPU_WORKER_ID": str(worker_id),
                "TPU_NAME": sl.name,
            })
            cmd = [sys.executable, "-m", "ray_tpu.core.raylet",
                   "--gcs", self.gcs_address, "--node-id", node_id,
                   "--resources", json.dumps(nt.host_resources())]
            proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                    stderr=subprocess.DEVNULL, env=env)
            _wait_for_line(proc, r"RAYLET_ADDRESS=(\S+)")
            sl.procs.append(proc)
            self._all_procs.append(proc)
            sl.host_node_ids.append(node_id)

    def get_slice(self, name: str) -> Optional[dict]:
        sl = self.slices.get(name)
        if sl is None:
            return None
        return {"name": name, "state": sl.state,
                "hosts": sl.host_node_ids or sl.node_type.num_hosts}

    def delete_slice(self, name: str) -> None:
        sl = self.slices.pop(name, None)
        if sl is None:
            return
        procs.end_processes(sl.procs, grace_s=procs.RAYLET_GRACE_S)

    def list_slices(self) -> List[dict]:
        return [self.get_slice(n) for n in list(self.slices)]

    def shutdown(self) -> None:
        for name in list(self.slices):
            self.delete_slice(name)
        # Belt-and-braces: anything ever spawned dies with the fake —
        # a slice deleted mid-provisioning can otherwise strand hosts.
        procs.end_processes(self._all_procs, grace_s=procs.RAYLET_GRACE_S)
        self._all_procs.clear()


class GcpTpuPodProvider(NodeProvider):
    """NodeProvider whose unit is one whole TPU slice."""

    def __init__(self, api: GcpTpuApi, name_prefix: str = "ray-tpu"):
        self.api = api
        self._prefix = name_prefix
        self._counter = 0

    def create_node(self, node_type: NodeType) -> str:
        if not isinstance(node_type, TpuSliceNodeType):
            raise TypeError(
                "GcpTpuPodProvider launches TpuSliceNodeType slices; got "
                f"{type(node_type).__name__}")
        self._counter += 1
        name = f"{self._prefix}-{node_type.accelerator_type}-{self._counter}"
        self.api.create_slice(name, node_type)
        logger.info("provisioned TPU slice %s (%d hosts)", name,
                    node_type.num_hosts)
        return name

    def terminate_node(self, node_id: str) -> None:
        self.api.delete_slice(node_id)

    def non_terminated_nodes(self) -> List[str]:
        return [s["name"] for s in self.api.list_slices()
                if s and s.get("state") in ("ACTIVE", "PROVISIONING")]

    def hosts_of(self, node_id: str) -> List[str]:
        """GCS node ids of this slice's hosts (one raylet per host). The
        autoscaler uses this to judge slice idleness across ALL hosts —
        a slice with one busy host is busy."""
        info = self.api.get_slice(node_id)
        if info is None:
            return []
        hosts = info.get("hosts")
        return hosts if isinstance(hosts, list) else []
