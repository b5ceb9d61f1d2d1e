"""Distribution-layer tests on the virtual 8-device CPU mesh: halo
exactness, all-to-all plumbing, and distributed-vs-single-device render
parity (the checks the reference could only do by eyeballing cluster runs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from scenery_insitu_tpu.config import CompositeConfig, RenderConfig, VDIConfig
from jax import shard_map
from scenery_insitu_tpu.core.camera import Camera
from scenery_insitu_tpu.core.transfer import TransferFunction
from scenery_insitu_tpu.core.vdi import render_vdi_same_view
from scenery_insitu_tpu.core.volume import Volume, procedural_volume
from scenery_insitu_tpu.ops.raycast import raycast
from scenery_insitu_tpu.parallel.mesh import (halo_exchange_z, make_mesh,
                                              volume_sharding)
from scenery_insitu_tpu.parallel.pipeline import (distributed_plain_step,
                                                  distributed_vdi_step,
                                                  shard_volume)
from scenery_insitu_tpu.utils.image import psnr

W = H = 16
STEPS = 48


def _cam():
    return Camera.create((0.0, 0.2, 4.0), fov_y_deg=50.0, near=0.5, far=20.0)


def _tf():
    return TransferFunction.ramp(0.05, 0.8, 0.7)


def test_mesh_creation():
    mesh = make_mesh(4)
    assert mesh.shape["ranks"] == 4
    mesh8 = make_mesh()
    assert mesh8.shape["ranks"] == 8


def test_halo_exchange_matches_global():
    mesh = make_mesh(4)
    d = 8
    data = jnp.arange(d * 2 * 2, dtype=jnp.float32).reshape(d, 2, 2)

    f = jax.jit(shard_map(
        lambda x: halo_exchange_z(x),
        mesh=mesh, in_specs=P("ranks", None, None),
        out_specs=P("ranks", None, None), check_vma=False))
    out = np.asarray(f(data))                     # [4*(2+2), 2, 2] stacked
    dn = d // 4
    blocks = out.reshape(4, dn + 2, 2, 2)
    gd = np.asarray(data)
    for r in range(4):
        lo = max(r * dn - 1, 0)
        hi = min((r + 1) * dn + 1, d)
        expect = gd[lo:hi]
        if r == 0:
            expect = np.concatenate([gd[:1], expect], axis=0)
        if r == 3:
            expect = np.concatenate([expect, gd[-1:]], axis=0)
        assert np.array_equal(blocks[r], expect), r


def test_shard_volume_layout():
    mesh = make_mesh(4)
    data = jnp.zeros((8, 4, 4))
    sharded = shard_volume(data, mesh)
    assert sharded.sharding == volume_sharding(mesh)


@pytest.mark.parametrize("n,background", [(2, (0, 0, 0, 0)), (4, (0, 0, 0, 0)),
                                          (4, (1.0, 0.2, 0.1, 1.0))])
def test_distributed_plain_matches_single(n, background):
    mesh = make_mesh(n)
    vol = procedural_volume(16, kind="shell")
    cfg = RenderConfig(max_steps=STEPS, early_exit_alpha=1.1,
                       background=background)
    cam = _cam()
    ref = np.asarray(raycast(vol, _tf(), cam, W, H, cfg).image)

    step = distributed_plain_step(mesh, _tf(), W, H, cfg)
    img = np.asarray(step(shard_volume(vol.data, mesh), vol.origin,
                          vol.spacing, cam))
    assert img.shape == (4, H, W)
    assert psnr(ref, img) > 28.0, psnr(ref, img)


def test_distributed_vdi_matches_single():
    n = 4
    mesh = make_mesh(n)
    vol = procedural_volume(16, kind="blobs")
    cam = _cam()
    ref = np.asarray(raycast(vol, _tf(), cam, W, H,
                             RenderConfig(max_steps=STEPS,
                                          early_exit_alpha=1.1)).image)
    step = distributed_vdi_step(
        mesh, _tf(), W, H,
        VDIConfig(max_supersegments=10, adaptive_iters=4),
        CompositeConfig(max_output_supersegments=16), max_steps=STEPS)
    vdi = step(shard_volume(vol.data, mesh), vol.origin, vol.spacing, cam)
    assert vdi.color.shape == (16, 4, H, W)
    img = np.asarray(render_vdi_same_view(vdi))
    assert psnr(ref, img) > 25.0, psnr(ref, img)


@pytest.mark.parametrize("k_out,spec", [
    # the ranks divide the slots: the frame leaves the mesh slot-major,
    # each rank holding whole rows of its K/n slots (pipeline._frame_out)
    (16, P("ranks", None, None, None)),
    # they do not: W-sharded as composited, each rank its column block
    (5, P(None, None, None, "ranks"))])
def test_distributed_vdi_output_sharding(k_out, spec):
    from jax.sharding import NamedSharding

    mesh = make_mesh(2)
    vol = procedural_volume(8)
    step = distributed_vdi_step(mesh, _tf(), W, H,
                                VDIConfig(max_supersegments=6,
                                          adaptive=False, threshold=0.1),
                                CompositeConfig(
                                    max_output_supersegments=k_out),
                                max_steps=16)
    vdi = step(shard_volume(vol.data, mesh), vol.origin, vol.spacing, _cam())
    assert vdi.color.shape == (k_out, 4, H, W)
    for leaf in (vdi.color, vdi.depth):
        assert leaf.sharding.is_equivalent_to(NamedSharding(mesh, spec),
                                              leaf.ndim), leaf.sharding


def test_width_divisibility_check():
    mesh = make_mesh(4)
    with pytest.raises(ValueError):
        distributed_vdi_step(mesh, _tf(), 18, H)


@pytest.mark.parametrize("eye", [(0.0, 0.2, 4.0),    # march axis z (sharded)
                                 (3.8, 0.3, 0.6)])   # march axis x (in-plane z)
def test_distributed_vdi_mxu_matches_single(eye):
    """MXU slice-march distributed pipeline vs single-device MXU VDI:
    both march regimes (domain axis and in-plane-z with halo+ownership)."""
    from scenery_insitu_tpu.config import SliceMarchConfig
    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.ops.vdi_render import render_vdi
    from scenery_insitu_tpu.parallel.pipeline import distributed_vdi_step_mxu

    n = 4
    mesh = make_mesh(n)
    vol = procedural_volume(16, kind="blobs")
    cam = Camera.create(eye, fov_y_deg=50.0, near=0.5, far=20.0)
    tf = _tf()
    cfg = VDIConfig(max_supersegments=10, adaptive_iters=4)

    spec = slicer.make_spec(cam, vol.data.shape,
                            SliceMarchConfig(matmul_dtype="f32", scale=1.5))
    # single-device reference through the same engine
    vdi_s, meta_s, _ = slicer.generate_vdi_mxu(vol, tf, cam, spec, cfg)
    ref = np.asarray(render_vdi(vdi_s, meta_s, cam, W, H, steps=STEPS))

    step = distributed_vdi_step_mxu(
        mesh, tf, spec, cfg, CompositeConfig(max_output_supersegments=16))
    vdi, meta = step(shard_volume(vol.data, mesh), vol.origin, vol.spacing,
                     cam)
    assert vdi.color.shape == (16, 4, spec.nj, spec.ni)
    img = np.asarray(render_vdi(vdi, meta, cam, W, H, steps=STEPS))
    q = psnr(ref, img)
    assert q > 27.0, f"PSNR {q:.1f} dB at eye {eye}"


@pytest.mark.parametrize("eye", [(0.0, 0.2, 4.0),    # march axis z (sharded)
                                 (3.8, 0.3, 0.6)])   # march axis x (in-plane z)
def test_distributed_vdi_mxu_temporal_matches_histogram(eye):
    """Distributed temporal mode (per-rank carried threshold, one march
    per frame) converges to the same composited VDI quality as the
    per-frame histogram mode, in both march regimes."""
    from scenery_insitu_tpu.config import SliceMarchConfig
    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.ops.vdi_render import render_vdi
    from scenery_insitu_tpu.parallel.pipeline import (
        distributed_initial_threshold_mxu, distributed_vdi_step_mxu,
        distributed_vdi_step_mxu_temporal)

    n = 4
    mesh = make_mesh(n)
    vol = procedural_volume(16, kind="blobs")
    cam = Camera.create(eye, fov_y_deg=50.0, near=0.5, far=20.0)
    tf = _tf()
    comp = CompositeConfig(max_output_supersegments=16)
    spec = slicer.make_spec(cam, vol.data.shape,
                            SliceMarchConfig(matmul_dtype="f32", scale=1.5))
    data = shard_volume(vol.data, mesh)

    cfg_h = VDIConfig(max_supersegments=10, adaptive_mode="histogram")
    vdi_h, meta_h = distributed_vdi_step_mxu(mesh, tf, spec, cfg_h, comp)(
        data, vol.origin, vol.spacing, cam)
    ref = np.asarray(render_vdi(vdi_h, meta_h, cam, W, H, steps=STEPS))

    cfg_t = VDIConfig(max_supersegments=10, adaptive_mode="temporal")
    thr = distributed_initial_threshold_mxu(mesh, tf, spec, cfg_t)(
        data, vol.origin, vol.spacing, cam)
    assert thr.thr.shape == (n * spec.nj, spec.ni)   # rank-stacked maps
    step_t = distributed_vdi_step_mxu_temporal(mesh, tf, spec, cfg_t, comp)
    for _ in range(3):
        (vdi_t, meta_t), thr = step_t(data, vol.origin, vol.spacing, cam,
                                      thr)
    img = np.asarray(render_vdi(vdi_t, meta_t, cam, W, H, steps=STEPS))
    assert np.isfinite(img).all()
    q = psnr(ref, img)
    assert q > 27.0, f"PSNR {q:.1f} dB at eye {eye}"


@pytest.mark.parametrize("eye", [(0.0, 0.2, 4.0),    # march axis z (sharded)
                                 (3.8, 0.3, 0.6)])   # march axis x (in-plane z)
def test_distributed_plain_mxu_matches_single(eye):
    """Distributed MXU plain-image mode (render_slices + column exchange +
    nearest-first composite + display warp) vs the single-device MXU
    renderer — both march regimes (≅ the reference's plain pipeline,
    DistributedVolumeRenderer.kt:175-189, on the slice-march engine)."""
    from scenery_insitu_tpu.config import SliceMarchConfig
    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.parallel.pipeline import (
        distributed_plain_step_mxu)

    n = 4
    mesh = make_mesh(n)
    vol = procedural_volume(16, kind="blobs")
    cam = Camera.create(eye, fov_y_deg=50.0, near=0.5, far=20.0)
    tf = _tf()
    bg = (0.1, 0.2, 0.3, 1.0)
    spec = slicer.make_spec(cam, vol.data.shape,
                            SliceMarchConfig(matmul_dtype="f32", scale=1.5),
                            multiple_of=n)

    ref = np.asarray(slicer.raycast_mxu(vol, tf, cam, W, H, spec,
                                        background=bg).image)

    step = distributed_plain_step_mxu(mesh, tf, spec)
    img_i, axcam = step(shard_volume(vol.data, mesh), vol.origin,
                        vol.spacing, cam)
    assert img_i.shape == (4, spec.nj, spec.ni)
    img = np.asarray(slicer.warp_to_camera(img_i, axcam, spec, cam, W, H,
                                           bg))
    q = psnr(ref, img)
    assert q > 32.0, f"PSNR {q:.1f} dB at eye {eye}"


def test_distributed_vdi_mxu_with_vtiles():
    """In-plane occupancy tiles composed with the distributed MXU VDI
    pipeline: each rank re-clamps the tile count against its own slab's
    v extent (which is far below the global clamp when marching across
    the sharded axis), and the result must match the untiled pipeline
    exactly (conservative gating)."""
    from scenery_insitu_tpu.config import (CompositeConfig,
                                           SliceMarchConfig, VDIConfig)
    from scenery_insitu_tpu.ops import slicer as slc
    from scenery_insitu_tpu.parallel.pipeline import distributed_vdi_step_mxu

    n = 4
    mesh = make_mesh(n)
    data = np.zeros((32, 32, 32), np.float32)
    data[6:18, 4:14, 8:20] = 0.7
    vol = Volume.centered(jnp.asarray(data), extent=2.0)
    cam = Camera.create((0.1, 2.9, 0.3), fov_y_deg=45.0, near=0.3,
                        far=10.0)   # looks down -y: marches ACROSS z shards
    vdi_cfg = VDIConfig(max_supersegments=4, adaptive_iters=2)
    comp_cfg = CompositeConfig(max_output_supersegments=6, adaptive_iters=2)

    outs = {}
    for vt in (0, 8):
        spec = slc.make_spec(cam, vol.data.shape,
                             SliceMarchConfig(matmul_dtype="f32", scale=1.0,
                                              occupancy_vtiles=vt),
                             multiple_of=n)
        step = distributed_vdi_step_mxu(mesh, _tf(), spec, vdi_cfg,
                                        comp_cfg)
        vdi, _ = step(shard_volume(vol.data, mesh), vol.origin,
                      vol.spacing, cam)
        outs[vt] = (np.asarray(vdi.color), np.asarray(vdi.depth))
    # block-split einsums fuse differently than the single einsum -> fp
    # association noise ~1e-7; a DROPPED block would differ by whole
    # sample values (~1e-1), so this tight bound still proves the gate
    # is conservative
    np.testing.assert_allclose(outs[8][0], outs[0][0], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(outs[8][1], outs[0][1], rtol=1e-5,
                               atol=1e-6)
