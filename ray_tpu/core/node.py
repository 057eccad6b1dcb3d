"""Node process supervisor: spawns and babysits GCS + raylet.

Reference equivalent: `python/ray/_private/node.py:38` (`Node`,
`start_gcs_server :1103`, `start_raylet :1134`, `start_head_processes
:1300`). Session layout mirrors the reference: a per-session directory with
process logs.
"""

from __future__ import annotations

import atexit
import json
import os
import re
import subprocess
import sys
import time
from typing import Dict, Optional

from ray_tpu.core import procs
from ray_tpu.core.ids import NodeID


def detect_node_resources(num_cpus: Optional[int] = None,
                          num_gpus: Optional[int] = None,
                          resources: Optional[Dict[str, float]] = None
                          ) -> Dict[str, float]:
    """CPU/memory autodetection plus TPU chips as a first-class resource
    (reference: _private/accelerators/tpu.py — but pod-aware here)."""
    out: Dict[str, float] = {}
    out["CPU"] = float(num_cpus if num_cpus is not None
                       else (os.cpu_count() or 1))
    if num_gpus:
        out["GPU"] = float(num_gpus)
    try:
        import psutil
        out["memory"] = float(psutil.virtual_memory().available)
    except Exception:
        out["memory"] = 4e9
    try:
        from ray_tpu.parallel.tpu import local_tpu_resources
        out.update(local_tpu_resources())
    except Exception:
        pass
    out.update(resources or {})
    return out


def _wait_for_line(proc: subprocess.Popen, pattern: str,
                   timeout: float = 30.0) -> str:
    """Read stdout lines until one matches `pattern`; returns the match."""
    regex = re.compile(pattern)
    deadline = time.time() + timeout
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"process exited with code {proc.returncode} before "
                    f"printing {pattern!r}")
            time.sleep(0.05)
            continue
        text = line.decode(errors="replace").strip()
        m = regex.search(text)
        if m:
            return m.group(1)
    raise TimeoutError(f"timed out waiting for {pattern!r}")


class NodeSupervisor:
    def __init__(self, session_dir: str):
        self.session_dir = session_dir
        self.log_dir = os.path.join(session_dir, "logs")
        os.makedirs(self.log_dir, exist_ok=True)
        self.processes: Dict[str, subprocess.Popen] = {}
        self.gcs_address: Optional[str] = None
        self.raylet_address: Optional[str] = None
        self.dashboard_address: Optional[str] = None
        self.node_id: Optional[str] = None
        atexit.register(self.stop)

    # -- head bring-up (reference: node.py start_head_processes) ---------
    @classmethod
    def start_head(cls, num_cpus=None, num_gpus=None, resources=None,
                   object_store_memory=None,
                   session_root: str = "/tmp/ray_tpu_sessions",
                   include_dashboard: bool = True) -> "NodeSupervisor":
        session_dir = os.path.join(
            session_root, f"session_{time.strftime('%Y%m%d-%H%M%S')}_"
                          f"{os.getpid()}")
        node = cls(session_dir)
        node._start_gcs()
        node._start_raylet(
            detect_node_resources(num_cpus, num_gpus, resources),
            object_store_memory, is_head=True)
        if include_dashboard:
            node._start_dashboard()
        return node

    def _child_env(self) -> dict:
        env = dict(os.environ)
        env["RAY_TPU_LOG_DIR"] = self.log_dir
        return env

    def _spawn(self, name: str, cmd, pattern: str) -> str:
        log = open(os.path.join(self.log_dir, f"{name}.err"), "ab")
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, stderr=log,
                                env=self._child_env())
        self.processes[name] = proc
        return _wait_for_line(proc, pattern)

    def _start_gcs(self) -> None:
        self.gcs_address = self._spawn(
            "gcs", [sys.executable, "-m", "ray_tpu.core.gcs.server",
                    "--storage",
                    os.path.join(self.session_dir, "gcs_storage.pkl")],
            r"GCS_ADDRESS=(\S+)")

    def _start_raylet(self, resources: Dict[str, float],
                      object_store_memory: Optional[int],
                      is_head: bool = False) -> None:
        self.node_id = NodeID.from_random().hex()
        cmd = [sys.executable, "-m", "ray_tpu.core.raylet",
               "--gcs", self.gcs_address, "--node-id", self.node_id,
               "--resources", json.dumps(resources)]
        if object_store_memory:
            cmd += ["--object-store-memory", str(object_store_memory)]
        if is_head:
            cmd += ["--head"]
        self.raylet_address = self._spawn(
            "raylet", cmd, r"RAYLET_ADDRESS=(\S+)")

    def kill_gcs(self) -> None:
        """Fault injection: hard-kill the GCS process (reference:
        test_gcs_fault_tolerance.py)."""
        proc = self.processes["gcs"]
        proc.kill()
        proc.wait()

    def restart_gcs(self) -> None:
        """Bring the GCS back at the SAME address with its persisted
        storage; raylets re-register via the heartbeat False-reply
        contract, clients reconnect via _ReconnectingRpc."""
        host, port = self.gcs_address.rsplit(":", 1)
        addr = self._spawn(
            "gcs", [sys.executable, "-m", "ray_tpu.core.gcs.server",
                    "--host", host, "--port", port, "--storage",
                    os.path.join(self.session_dir, "gcs_storage.pkl")],
            r"GCS_ADDRESS=(\S+)")
        assert addr == self.gcs_address, (addr, self.gcs_address)

    def _start_dashboard(self) -> None:
        """Observability HTTP head (reference: dashboard/head.py). A
        dashboard failure must never block cluster bring-up."""
        try:
            self.dashboard_address = self._spawn(
                "dashboard",
                [sys.executable, "-m", "ray_tpu.dashboard",
                 "--gcs", self.gcs_address],
                r"DASHBOARD_READY (\S+)")
        except Exception:
            self.dashboard_address = None

    def stop(self) -> None:
        # One shared grace, sized for the slowest: a raylet waits for its
        # own workers before it leaves, and is not killed over that.
        procs.end_processes(reversed(list(self.processes.values())),
                            grace_s=procs.RAYLET_GRACE_S)
        self.processes.clear()
