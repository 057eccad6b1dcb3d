"""Multi-slice hybrid (ICI x DCN) mesh layout.

Reference: none — Ray has no multi-slice mesh story; the layout contract
is the scaling-book recipe (dp outermost across slices so only the
per-step gradient reduction crosses DCN). The 2-process jax.distributed
end-to-end run lives in `__graft_entry__._dryrun_2slice` (driver-executed
each round); these tests pin the *device-placement* invariants
single-process.
"""

import numpy as np
import pytest

import jax

from ray_tpu.parallel.mesh import make_hybrid_mesh, make_mesh, slice_id_of


class _FakeSliceDev:
    """CPU-like device stand-in: the slice is the owning process, which
    is the layout the CPU branch of the mesh builders handles."""

    platform = "cpu"

    def __init__(self, id_, slice_index):
        self.id = id_
        self.slice_index = self.process_index = slice_index

    def __repr__(self):
        return f"dev{self.id}@s{self.slice_index}"


class _FakeTpuDev(_FakeSliceDev):
    """TPU-like stand-in with no topology `mesh_utils` could read."""

    platform = "tpu"


def _fake_devices(n_slices, per_slice):
    return [_FakeSliceDev(s * per_slice + i, s)
            for s in range(n_slices) for i in range(per_slice)]


def test_slice_id_prefers_slice_index_on_tpu():
    dev = _FakeTpuDev(0, 3)
    dev.process_index = 0
    assert slice_id_of(dev) == 3


def test_tpu_devices_never_get_the_id_order_layout():
    """A topology-aware layout that fails on TPU devices is an error;
    the plain reshape is for CPU devices only."""
    devs = [_FakeTpuDev(i, i // 4) for i in range(8)]
    # mesh_utils reads the topology off the device and the fake has none.
    with pytest.raises(AttributeError, match="device_kind"):
        make_mesh((4, 1, 1, 2), devices=devs)
    with pytest.raises(AttributeError, match="device_kind"):
        make_hybrid_mesh((4, 1, 1, 2), devices=devs)


def test_slice_id_uses_process_index_on_cpu():
    # CPU devices carry a constant slice_index=0; the process boundary is
    # the DCN boundary there.
    d = jax.devices("cpu")[0]
    assert slice_id_of(d) == d.process_index


def test_dp_outer_blocks_align_with_slices():
    devs = _fake_devices(2, 4)
    mesh = make_hybrid_mesh((4, 1, 1, 2), devices=devs)
    arr = np.asarray(mesh.devices)        # [dp=4, pp=1, sp=1, tp=2]
    # dp rows 0-1 must be slice 0, rows 2-3 slice 1: the gradient
    # all-reduce segments that cross the slice boundary are exactly the
    # dp-outer halves (DCN), everything else stays intra-slice (ICI).
    for dp_idx in range(4):
        slice_ids = {d.slice_index for d in arr[dp_idx].flat}
        assert len(slice_ids) == 1, f"dp row {dp_idx} spans slices"
        assert slice_ids.pop() == dp_idx // 2
    # tp pairs never cross a slice.
    for dp_idx in range(4):
        row = arr[dp_idx, 0, 0, :]
        assert row[0].slice_index == row[1].slice_index


def test_default_shape_absorbs_slices_into_dp():
    devs = _fake_devices(2, 4)
    mesh = make_hybrid_mesh(devices=devs)
    # per-slice factorization is (1,1,2,2)-ish via mesh_shape_for(4);
    # dp must be doubled by the slice count.
    assert mesh.shape["dp"] % 2 == 0
    assert np.prod(list(mesh.shape.values())) == 8


def test_rejects_dp_not_multiple_of_slices():
    devs = _fake_devices(2, 4)
    with pytest.raises(ValueError, match="multiple of the slice count"):
        make_hybrid_mesh((3, 1, 1, 2), devices=devs)


def test_rejects_model_axis_spanning_slices():
    devs = _fake_devices(2, 4)
    # tp=8 cannot fit in a 4-device slice.
    with pytest.raises(ValueError):
        make_hybrid_mesh((1, 1, 1, 8), devices=devs)


def test_single_slice_falls_back_cleanly():
    # All devices in one "slice": hybrid mesh == plain mesh semantics.
    devs = _fake_devices(1, 8)
    mesh = make_hybrid_mesh((4, 1, 1, 2), devices=devs)
    plain = make_mesh((4, 1, 1, 2), devices=devs)
    assert [d.id for d in np.asarray(mesh.devices).flat] == \
           [d.id for d in np.asarray(plain.devices).flat]


def test_train_get_mesh_on_cpu_single_process():
    from ray_tpu.train import get_mesh

    mesh = get_mesh((8, 1, 1, 1), devices=jax.devices("cpu"))
    assert mesh.shape["dp"] == 8


def test_train_get_mesh_hybrid_on_fake_slices():
    from ray_tpu.train import get_mesh

    mesh = get_mesh((4, 1, 1, 2), devices=_fake_devices(2, 4))
    arr = np.asarray(mesh.devices)
    assert {d.slice_index for d in arr[0].flat} == {0}
    assert {d.slice_index for d in arr[3].flat} == {1}
