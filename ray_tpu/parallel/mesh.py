"""Device-mesh construction for the canonical parallelism axes.

The framework's standard mesh axes (SURVEY.md §2.5, §7.6):

- ``dp``  — data parallel; also the FSDP/ZeRO shard axis (params sharded over
  ``dp``; XLA's SPMD partitioner generates the reduce-scatter/all-gather
  pattern automatically) and the expert-parallel axis (experts sharded over
  ``dp``, tokens all-to-all'd — the common ep_size == dp_size configuration).
- ``pp``  — pipeline stages (gpipe schedule via shard_map + ppermute).
- ``sp``  — sequence/context parallel (ring attention over ICI neighbors).
- ``tp``  — tensor parallel (Megatron-style row/col sharding).

On real hardware the mesh follows the physical topology
(`jax.experimental.mesh_utils.create_device_mesh`, whose failure is an
error); on CPU test backends we reshape the flat device list.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import numpy as np

AXES = ("dp", "pp", "sp", "tp")


@dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape; -1 on dp means 'absorb remaining devices'."""

    dp: int = -1
    pp: int = 1
    sp: int = 1
    tp: int = 1

    def resolve(self, n_devices: int) -> Tuple[int, int, int, int]:
        fixed = self.pp * self.sp * self.tp
        dp = self.dp
        if dp == -1:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by pp*sp*tp={fixed}")
            dp = n_devices // fixed
        if dp * fixed != n_devices:
            raise ValueError(
                f"Mesh {dp}x{self.pp}x{self.sp}x{self.tp} != {n_devices} devices")
        return (dp, self.pp, self.sp, self.tp)


def mesh_shape_for(n_devices: int) -> Tuple[int, int, int, int]:
    """Factorize n devices over (dp, pp, sp, tp), spreading across as many
    axes as possible so every parallelism mode is exercised: factors are dealt
    to tp, pp, sp, then dp absorbs the rest."""
    remaining = n_devices
    shape = {"dp": 1, "pp": 1, "sp": 1, "tp": 1}
    for axis in ("tp", "pp", "sp"):
        if remaining % 2 == 0 and remaining > 1:
            shape[axis] *= 2
            remaining //= 2
    shape["dp"] = remaining
    return (shape["dp"], shape["pp"], shape["sp"], shape["tp"])


def make_mesh(shape: Optional[Sequence[int]] = None,
              *, devices=None, axis_names: Sequence[str] = AXES):
    """Build a `jax.sharding.Mesh` with the canonical axis names."""
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if shape is None:
        shape = mesh_shape_for(n)
    shape = tuple(shape)
    if int(np.prod(shape)) != n:
        raise ValueError(f"mesh shape {shape} != {n} devices")
    if devices[0].platform == "cpu":
        # CPU devices have no topology to follow.
        arr = np.array(devices).reshape(shape)
    else:
        from jax.experimental import mesh_utils

        arr = mesh_utils.create_device_mesh(shape, devices=devices)
    return Mesh(arr, tuple(axis_names))


def auto_mesh(n_devices: Optional[int] = None, **axis_sizes):
    """`auto_mesh(8)` or `auto_mesh(dp=2, tp=4)`."""
    import jax

    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    if axis_sizes:
        spec = MeshSpec(**axis_sizes)
        return make_mesh(spec.resolve(len(devices)), devices=devices)
    return make_mesh(devices=devices)


def slice_id_of(device) -> int:
    """Which TPU slice (ICI domain) a device belongs to. TPU devices carry
    a meaningful `slice_index`; on CPU/test backends the attribute exists
    but is a constant 0, so each host process is its own "slice"
    (DCN-connected) — exactly the multi-slice topology the hybrid mesh
    models."""
    if getattr(device, "platform", None) == "tpu":
        sid = getattr(device, "slice_index", None)
        if sid is not None:
            return int(sid)
    return int(getattr(device, "process_index", 0))


def make_hybrid_mesh(shape: Optional[Sequence[int]] = None, *,
                     devices=None, axis_names: Sequence[str] = AXES):
    """Multi-slice (ICI x DCN) mesh: ``dp`` spans slices over DCN, the
    model axes (pp/sp/tp) stay inside a slice on ICI.

    Multi-slice TPU pods have two interconnect tiers — chips within a
    slice talk over ICI (~100s of GB/s), slices talk over DCN (~10s of
    Gb/s per host). Collectives must be laid out so the *frequent, large*
    ones (tensor/sequence/pipeline) ride ICI and only the once-per-step
    gradient all-reduce crosses DCN: that is dp-outermost across slices
    (scaling-book recipe; no reference implementation exists — Ray has no
    multi-slice story).

    `shape` is the GLOBAL (dp, pp, sp, tp); dp must be a multiple of the
    slice count, every other axis must fit within one slice. Device order
    is built slice-major so the dp axis's outer blocks align with slice
    boundaries; XLA then routes each axis's collectives over the right
    fabric.
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    by_slice: dict = {}
    for d in devices:
        by_slice.setdefault(slice_id_of(d), []).append(d)
    n_slices = len(by_slice)
    per_slice = len(devices) // n_slices
    if any(len(v) != per_slice for v in by_slice.values()):
        raise ValueError(
            f"uneven slices: {[len(v) for v in by_slice.values()]}")
    if shape is None:
        inner = mesh_shape_for(per_slice)
        shape = (inner[0] * n_slices, *inner[1:])
    dp, pp, sp, tp = shape
    if dp % n_slices != 0:
        raise ValueError(
            f"dp={dp} must be a multiple of the slice count {n_slices}")
    if pp * sp * tp * (dp // n_slices) != per_slice:
        raise ValueError(
            f"per-slice shape dp/slices x pp x sp x tp = "
            f"{dp // n_slices}x{pp}x{sp}x{tp} != {per_slice} "
            f"devices per slice")
    if devices[0].platform == "cpu":
        # CPU devices have no topology to follow: slice-major ordering,
        # dp split into (slice, dp_inner) then flattened so slice is the
        # OUTER dp factor.
        ordered = [d for sid in sorted(by_slice)
                   for d in sorted(by_slice[sid], key=lambda d: d.id)]
        arr = np.array(ordered).reshape(
            n_slices, dp // n_slices, pp, sp, tp).reshape(dp, pp, sp, tp)
    else:
        from jax.experimental import mesh_utils

        arr = mesh_utils.create_hybrid_device_mesh(
            (dp // n_slices, pp, sp, tp), (n_slices, 1, 1, 1),
            devices=devices)
    return Mesh(arr, tuple(axis_names))
