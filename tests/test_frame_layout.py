"""How a composited VDI frame leaves the mesh (ISSUE 40): re-sharded from
column blocks to slot blocks where `pipeline._frame_out` can, so that the
host that takes it shard by shard copies whole contiguous blocks. The move
is exact: the frame is bit-equal, leaf by leaf, to the W-sharded frame of
the same step, and every leaf's placement is what the helper said."""

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from scenery_insitu_tpu.config import (CompositeConfig, SliceMarchConfig,
                                       TopologyConfig, VDIConfig)
from scenery_insitu_tpu.core.camera import Camera
from scenery_insitu_tpu.core.transfer import TransferFunction
from scenery_insitu_tpu.core.volume import procedural_volume
from scenery_insitu_tpu.ops import slicer
from scenery_insitu_tpu.parallel import pipeline
from scenery_insitu_tpu.parallel.mesh import make_mesh
from scenery_insitu_tpu.parallel.topology import (make_topology_mesh,
                                                  resolve_mesh_topology)

W = H = 16


def _scene():
    vol = procedural_volume(16, kind="blobs")
    cam = Camera.create((0.0, 0.2, 4.0), fov_y_deg=50.0, near=0.5, far=20.0)
    return vol, cam, TransferFunction.ramp(0.05, 0.8, 0.7)


def _frame(kind, mesh, k_out, topology=None):
    """One composited frame of builder ``kind`` on ``mesh``, as the step
    returns it (device arrays)."""
    vol, cam, tf = _scene()
    comp = CompositeConfig(max_output_supersegments=k_out, adaptive_iters=2)
    data = pipeline.shard_volume(vol.data, mesh)
    args = (data, vol.origin, vol.spacing, cam)
    if kind == "gather":
        step = pipeline.distributed_vdi_step(
            mesh, tf, W, H, VDIConfig(max_supersegments=6, adaptive_iters=2),
            comp, max_steps=24, topology=topology)
        return step(*args)
    n = mesh.devices.size
    spec = slicer.make_spec(cam, vol.data.shape,
                            SliceMarchConfig(matmul_dtype="f32"),
                            multiple_of=n)
    if kind == "plain":
        step = pipeline.distributed_vdi_step_mxu(
            mesh, tf, spec, VDIConfig(max_supersegments=6, adaptive_iters=2),
            comp, topology=topology)
        return step(*args)[0]
    cfg = VDIConfig(max_supersegments=6, adaptive_mode="temporal")
    thr = pipeline.distributed_initial_threshold_mxu(mesh, tf, spec, cfg)(
        *args)
    step = pipeline.distributed_vdi_step_mxu_temporal(
        mesh, tf, spec, cfg, comp, topology=topology)
    for _ in range(2):          # the carried state rides through unchanged
        (vdi, _), thr = step(*args, thr)
    return vdi


def _placed(leaf, mesh, spec) -> bool:
    return leaf.sharding.is_equivalent_to(NamedSharding(mesh, spec),
                                          leaf.ndim)


def _same_bytes(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        assert x.shape == y.shape and x.dtype == y.dtype
        assert x.tobytes() == y.tobytes()


@pytest.mark.parametrize("kind", ["temporal", "plain", "gather"])
@pytest.mark.parametrize("n", [2, 4])
def test_slot_major_frame_is_the_w_sharded_frame(n, kind, monkeypatch):
    mesh = make_mesh(n)
    axis = mesh.axis_names[0]
    assert pipeline._leaves_slot_major(mesh, n, None, 16)
    got = _frame(kind, mesh, 16)
    monkeypatch.setattr(pipeline, "_leaves_slot_major",
                        lambda *a: False)
    want = _frame(kind, mesh, 16)
    for g, w in zip(got, want):
        assert g.shape == w.shape == (16, g.shape[1], g.shape[2],
                                      g.shape[3])
        assert _placed(g, mesh, P(axis, None, None, None))
        assert _placed(w, mesh, P(None, None, None, axis))
        assert {s.data.shape for s in g.addressable_shards} == {
            (16 // n,) + g.shape[1:]}
    _same_bytes(got, want)
    assert np.asarray(got.color).any()      # not an empty frame


@pytest.mark.parametrize("kind", ["temporal", "gather"])
def test_slots_the_ranks_do_not_divide_leave_w_sharded(kind, monkeypatch):
    mesh = make_mesh(4)
    axis = mesh.axis_names[0]
    assert not pipeline._leaves_slot_major(mesh, 4, None, 6)
    got = _frame(kind, mesh, 6)
    for leaf in got:
        assert _placed(leaf, mesh, P(None, None, None, axis))
    # the helper's other answer would have been refused, not mis-sharded
    monkeypatch.setattr(pipeline, "_leaves_slot_major", lambda *a: True)
    with pytest.raises(Exception):
        _frame(kind, mesh, 6)


def test_a_two_level_mesh_keeps_its_column_blocks():
    """The hierarchical composite hands its columns out ranks-major and
    its consumers are column tiles: the frame leaves W-sharded over
    ``topo.out_axis`` as before, equal to the flat mesh's frame."""
    tcfg = TopologyConfig(num_hosts=2)
    mesh, _ = make_topology_mesh(tcfg)
    flat_axis, n, topo = resolve_mesh_topology(mesh, topology=tcfg)
    assert topo is not None and 16 % n == 0
    assert not pipeline._leaves_slot_major(mesh, n, topo, 16)
    got = _frame("gather", mesh, 16, topology=tcfg)
    for leaf in got:
        assert _placed(leaf, mesh, P(None, None, None, topo.out_axis))
    flat = make_mesh(n)
    assert pipeline._leaves_slot_major(flat, n, None, 16)
    _same_bytes(got, _frame("gather", flat, 16))


@pytest.mark.parametrize("n,k_out,multi,want", [
    (4, 16, False, True), (2, 16, False, True), (4, 4, False, True),
    (4, 6, False, False), (8, 12, False, False), (1, 16, False, False),
    (4, 16, True, False)])
def test_the_helper_reads_slots_ranks_and_processes(n, k_out, multi, want,
                                                    monkeypatch):
    mesh = make_mesh(n)
    if multi:       # a mesh that spans processes gathers column blocks
        monkeypatch.setattr(type(mesh), "is_multi_process",
                            property(lambda self: True))
    axis = mesh.axis_names[0]
    assert pipeline._leaves_slot_major(mesh, n, None, k_out) is want
    specs, leave = pipeline._frame_out(mesh, axis, n, None, k_out)
    spec = (P(axis, None, None, None) if want
            else P(None, None, None, axis))
    assert specs.color == specs.depth == spec
