"""Training backends: the gang-wide process-group bootstrap seam.

Reference: `python/ray/train/_internal/backend_executor.py` Backend hooks +
`train/torch/config.py:151,171-190` where `_TorchBackend.on_start` wires
MASTER_ADDR/PORT and `dist.init_process_group("nccl")`. The TPU-native
replacement (`JaxConfig`) runs `jax.distributed.initialize(coordinator,
num_processes, process_id)` on every worker, so XLA collectives ride
ICI/DCN — no NCCL, no MASTER_ADDR.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional


class Backend:
    def on_start(self, worker_group, backend_config) -> None:
        pass

    def on_training_start(self, worker_group, backend_config) -> None:
        pass

    def on_shutdown(self, worker_group, backend_config) -> None:
        pass


@dataclass
class BackendConfig:
    @property
    def backend_cls(self):
        return Backend


def _setup_jax_distributed(coordinator: str, world_size: int, rank: int,
                           platform: Optional[str],
                           cpu_devices_per_worker: Optional[int]) -> bool:
    """Runs in each training worker before the gang uses JAX:
    `jax.distributed.initialize` must precede the backend that uses it,
    and a process opens its TPU client once."""
    import os

    if platform is None:
        platform = _leased_platform()
    if cpu_devices_per_worker and cpu_devices_per_worker > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                f"{cpu_devices_per_worker}").strip()

    import jax
    import jax.extend.backend

    # A pooled worker may have run JAX on the CPU for an earlier task,
    # and the distributed client must exist before the backend that
    # uses it.
    jax.extend.backend.clear_backends()
    if platform == "cpu":
        # Cross-process CPU collectives need the gloo transport
        # (the CPU analogue of the ICI fabric used on real slices).
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    # Explicit, so a platform that cannot be opened raises below instead
    # of falling through to another one.
    jax.config.update("jax_platforms", platform)
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=world_size, process_id=rank)
    got = jax.process_count(platform)
    if got != world_size:
        raise RuntimeError(f"jax world size {got} != {world_size}")
    return True


def _leased_platform() -> str:
    """The platform of a worker whose `JaxConfig` names none: what it was
    leased decides (`_apply_visible_chips` sets the variable for a chip
    lease), not what the host looks like."""
    import os

    return "tpu" if os.environ.get("TPU_VISIBLE_CHIPS") else "cpu"


def _teardown_jax_distributed() -> bool:
    import jax

    jax.distributed.shutdown()
    return True


@dataclass
class JaxConfig(BackendConfig):
    """Backend config for JAX SPMD training.

    platform: "cpu" to force the CPU backend (tests / CI without chips),
        "tpu" for real slices, None = "tpu" on a worker that was leased
        chips (`ScalingConfig(use_tpu=True)`) and "cpu" otherwise.
    cpu_devices_per_worker: virtual host devices per worker process when
        on CPU (`xla_force_host_platform_device_count`).
    """

    platform: Optional[str] = None
    cpu_devices_per_worker: Optional[int] = None

    @property
    def backend_cls(self):
        return _JaxBackend


class _JaxBackend(Backend):
    def on_start(self, worker_group, backend_config: JaxConfig) -> None:
        # A gang on one node meets on loopback: a sealed machine may not
        # resolve or route its own hostname.
        one_node = len({m["node_id"] for m in worker_group.metadata}) == 1
        coordinator = worker_group.execute_single(
            0, _free_port_on_worker, one_node)
        n = len(worker_group)
        import ray_tpu

        refs = []
        for rank, w in enumerate(worker_group.workers):
            refs.append(w.execute.remote(
                _setup_jax_distributed, coordinator, n, rank,
                backend_config.platform,
                backend_config.cpu_devices_per_worker))
        ray_tpu.get(refs, timeout=300)

    def on_shutdown(self, worker_group, backend_config: JaxConfig) -> None:
        try:
            worker_group.execute(_teardown_jax_distributed)
        except Exception:
            pass


def _free_port_on_worker(loopback: bool) -> str:
    import socket

    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    host = ("127.0.0.1" if loopback
            else socket.gethostbyname(socket.gethostname()))
    return f"{host}:{port}"
