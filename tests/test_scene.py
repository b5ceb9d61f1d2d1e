"""Multi-grid scene management (core/scene.py): uneven decompositions with
ghost layers must render identically to the assembled single volume —
the seam-exactness the reference gets from OpenFPM ghosts
(DistributedVolumeRenderer.kt:116-160)."""

import numpy as np
import pytest

from scenery_insitu_tpu.config import (CompositeConfig, RenderConfig,
                                       SliceMarchConfig, VDIConfig)
from scenery_insitu_tpu.core.camera import Camera
from scenery_insitu_tpu.core.scene import MultiGridScene
from scenery_insitu_tpu.core.transfer import for_dataset
from scenery_insitu_tpu.core.vdi import render_vdi_same_view
from scenery_insitu_tpu.core.volume import procedural_volume
from scenery_insitu_tpu.ops import slicer
from scenery_insitu_tpu.ops.raycast import raycast
from scenery_insitu_tpu.utils.image import psnr

VDI_CFG = VDIConfig(max_supersegments=6, adaptive_iters=2)
COMP_CFG = CompositeConfig(max_output_supersegments=8, adaptive_iters=2)
F32 = SliceMarchConfig(matmul_dtype="f32", scale=1.5)


@pytest.fixture(scope="module")
def vol():
    return procedural_volume(24, kind="blobs", seed=5)


@pytest.fixture(scope="module")
def tf():
    return for_dataset("procedural")


def _scene_z_split(vol, cuts):
    """Split a global volume into uneven z-slabs with 1-voxel ghosts."""
    scene = MultiGridScene()
    data = np.asarray(vol.data)
    d = data.shape[0]
    edges = [0] + list(cuts) + [d]
    for i, (z0, z1) in enumerate(zip(edges[:-1], edges[1:])):
        g_lo = 1 if z0 > 0 else 0
        g_hi = 1 if z1 < d else 0
        sub = data[z0 - g_lo:z1 + g_hi]
        origin = np.asarray(vol.origin) + np.array(
            [0, 0, (z0 - g_lo) * float(vol.spacing[2])], np.float32)
        scene.set_grid(0, i, sub, origin, vol.spacing,
                       ghost_lo=(0, 0, g_lo), ghost_hi=(0, 0, g_hi))
    return scene


def test_bookkeeping(vol):
    scene = _scene_z_split(vol, [7])
    assert scene.num_grids == 2
    scene.update_data(1, [np.asarray(vol.data)[:4]],
                      [np.asarray(vol.origin)], vol.spacing)
    assert scene.num_grids == 3
    scene.update_data(1, [], [], vol.spacing)
    assert scene.num_grids == 2
    lo, hi = scene.global_bounds()
    np.testing.assert_allclose(np.asarray(lo), np.asarray(vol.world_min),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(hi), np.asarray(vol.world_max),
                               atol=1e-6)


def test_plain_render_matches_single_volume(vol, tf):
    cam = Camera.create((0.3, 0.6, 2.8), fov_y_deg=45.0, near=0.3, far=10.0)
    cfg = RenderConfig(width=48, height=40, max_steps=64)
    ref = raycast(vol, tf, cam, 48, 40, cfg)
    scene = _scene_z_split(vol, [7, 15])       # uneven 7/8/9 split
    got = scene.render(tf, cam, 48, 40, cfg)
    p = psnr(np.asarray(got), np.asarray(ref.image))
    assert p > 35.0, f"multi-grid plain render diverges: {p:.1f} dB"


def test_vdi_gather_matches_single_volume(vol, tf):
    cam = Camera.create((0.2, 0.5, 2.9), fov_y_deg=45.0, near=0.3, far=10.0)
    from scenery_insitu_tpu.ops.vdi_gen import generate_vdi
    from scenery_insitu_tpu.ops.composite import composite_vdis
    ref_vdi, _ = generate_vdi(vol, tf, cam, 40, 32, VDI_CFG, max_steps=64)
    ref = composite_vdis(ref_vdi.color[None], ref_vdi.depth[None], COMP_CFG)
    scene = _scene_z_split(vol, [9])
    got, meta = scene.generate_vdi(tf, cam, 40, 32, VDI_CFG, COMP_CFG,
                                   max_steps=64)
    img_ref = np.asarray(render_vdi_same_view(ref))
    img_got = np.asarray(render_vdi_same_view(got))
    p = psnr(img_got, img_ref)
    assert p > 30.0, f"multi-grid VDI diverges: {p:.1f} dB"
    np.testing.assert_allclose(np.asarray(meta.volume_dims),
                               [24, 24, 24], atol=1e-4)


def test_vdi_mxu_matches_single_volume(vol, tf):
    """The flagship check: uneven multi-grid slice march ≅ one volume."""
    cam = Camera.create((0.1, 0.4, 2.8), fov_y_deg=45.0, near=0.3, far=10.0)
    spec = slicer.make_spec(cam, vol.data.shape, F32)
    from scenery_insitu_tpu.ops.composite import composite_vdis
    ref_vdi, _, _ = slicer.generate_vdi_mxu(vol, tf, cam, spec, VDI_CFG)
    ref = composite_vdis(ref_vdi.color[None], ref_vdi.depth[None], COMP_CFG)
    scene = _scene_z_split(vol, [5, 14])       # uneven 5/9/10 split
    got, _ = scene.generate_vdi_mxu(tf, cam, spec, VDI_CFG, COMP_CFG)
    img_ref = np.asarray(render_vdi_same_view(ref))
    img_got = np.asarray(render_vdi_same_view(got))
    p = psnr(img_got, img_ref)
    assert p > 30.0, f"multi-grid MXU VDI diverges: {p:.1f} dB"


def test_vdi_mxu_in_plane_split(vol, tf):
    """Grids split along an IN-PLANE axis (x) relative to a z-marching
    camera: exercises the u-bounds ownership + ghost-column path."""
    cam = Camera.create((0.0, 0.3, 2.8), fov_y_deg=45.0, near=0.3, far=10.0)
    spec = slicer.make_spec(cam, vol.data.shape, F32)
    assert spec.axis == 2
    from scenery_insitu_tpu.ops.composite import composite_vdis
    ref_vdi, _, _ = slicer.generate_vdi_mxu(vol, tf, cam, spec, VDI_CFG)
    ref = composite_vdis(ref_vdi.color[None], ref_vdi.depth[None], COMP_CFG)

    data = np.asarray(vol.data)
    w = data.shape[2]
    scene = MultiGridScene()
    for i, (x0, x1) in enumerate([(0, 10), (10, 24)]):   # uneven x split
        g_lo = 1 if x0 > 0 else 0
        g_hi = 1 if x1 < w else 0
        sub = data[:, :, x0 - g_lo:x1 + g_hi]
        origin = np.asarray(vol.origin) + np.array(
            [(x0 - g_lo) * float(vol.spacing[0]), 0, 0], np.float32)
        scene.set_grid(0, i, sub, origin, vol.spacing,
                       ghost_lo=(g_lo, 0, 0), ghost_hi=(g_hi, 0, 0))
    got, _ = scene.generate_vdi_mxu(tf, cam, spec, VDI_CFG, COMP_CFG)
    img_ref = np.asarray(render_vdi_same_view(ref))
    img_got = np.asarray(render_vdi_same_view(got))
    p = psnr(img_got, img_ref)
    assert p > 30.0, f"in-plane multi-grid MXU VDI diverges: {p:.1f} dB"


def test_scene_session_external_driver(vol, tf, tmp_path):
    """The external-driver loop: push grids through the updateData
    boundary, render frames, update a grid, render again (≅ OpenFPM
    driving the JNI callbacks between frames)."""
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.scene_session import SceneSession
    from scenery_insitu_tpu.runtime.session import png_sink

    cfg = FrameworkConfig().with_overrides(
        "render.width=32", "render.height=24", "render.max_steps=24",
        "vdi.max_supersegments=4", "vdi.adaptive_iters=1",
        "composite.max_output_supersegments=6", "composite.adaptive_iters=1",
        "slicer.engine=mxu", "slicer.matmul_dtype=f32",
        "runtime.dataset=procedural")
    sess = SceneSession(cfg, sinks=[png_sink(str(tmp_path))])

    import pytest as _pytest
    with _pytest.raises(RuntimeError, match="no grids"):
        sess.render_frame()

    data = np.asarray(vol.data)
    d = data.shape[0]
    halves = [(0, 11), (11, 24)]               # uneven
    grids, origins, glo, ghi = [], [], [], []
    for z0, z1 in halves:
        g0 = 1 if z0 > 0 else 0
        g1 = 1 if z1 < d else 0
        grids.append(data[z0 - g0:z1 + g1])
        origins.append(np.asarray(vol.origin)
                       + np.array([0, 0, (z0 - g0) * float(vol.spacing[2])],
                                  np.float32))
        glo.append((0, 0, g0))
        ghi.append((0, 0, g1))
    sess.update_data(0, grids, origins, vol.spacing, glo, ghi)

    p1 = sess.render_frame()
    assert p1["vdi_color"].shape[0] == 6
    assert np.isfinite(p1["vdi_color"]).all()

    # new timestep for grid 0 (≅ updateVolume)
    sess.update_grid(0, 0, grids[0] * 0.5)
    p2 = sess.render_frame()
    assert not np.array_equal(p1["vdi_color"], p2["vdi_color"])
    import glob as _glob
    assert len(_glob.glob(str(tmp_path / "frame*.png"))) == 2


def test_scene_session_temporal_mode(vol, tf):
    """SceneSession with adaptive_mode='temporal': threshold state is
    seeded on the first frame, threaded across frames, and re-seeded when
    the grid-set signature changes (repartition)."""
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.scene_session import SceneSession

    cfg = FrameworkConfig().with_overrides(
        "vdi.max_supersegments=4", "vdi.adaptive_mode=temporal",
        "composite.max_output_supersegments=6", "composite.adaptive_iters=1",
        "slicer.engine=mxu", "slicer.matmul_dtype=f32",
        "runtime.dataset=procedural")
    sess = SceneSession(cfg)
    assert sess._temporal

    data = np.asarray(vol.data)
    sess.update_data(0, [data], [np.asarray(vol.origin)], vol.spacing)
    p1 = sess.render_frame()
    assert np.isfinite(p1["vdi_color"]).all()
    assert len(sess._steps.thr) == 1
    thr1 = next(iter(sess._steps.thr.values()))
    assert thr1.thr.shape[0] == 1      # one grid

    p2 = sess.render_frame()        # carried state, same compiled step
    assert np.isfinite(p2["vdi_color"]).all()
    assert len(sess._steps.steps) == 1

    # moving the scene (same shapes, new extent) must recompile the step
    # (stale-spec guard) and seed a fresh threshold entry
    sess.update_data(0, [data], [np.asarray(vol.origin) + 1.5], vol.spacing)
    p3 = sess.render_frame()
    assert np.isfinite(p3["vdi_color"]).all()
    assert len(sess._steps.steps) == 2
    assert len(sess._steps.thr) == 2


def test_insitu_session_rejects_temporal():
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.session import InSituSession

    cfg = FrameworkConfig().with_overrides("vdi.adaptive_mode=temporal")
    with pytest.raises(ValueError, match="temporal"):
        InSituSession(cfg)


def test_scene_session_extent_cache_survives_update_grid(vol, tf):
    """update_grid replaces data only (origin/spacing unchanged), so the
    extent cache must NOT be invalidated — the canonical driver loop
    (update_grid every timestep, then render) would otherwise pay a
    device sync per dispatch. update_data CAN change layout and must
    invalidate."""
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.scene_session import SceneSession

    cfg = FrameworkConfig().with_overrides(
        "vdi.max_supersegments=4", "composite.max_output_supersegments=6",
        "slicer.engine=mxu", "slicer.matmul_dtype=f32",
        "runtime.dataset=procedural")
    sess = SceneSession(cfg)
    data = np.asarray(vol.data)
    sess.update_data(0, [data], [np.asarray(vol.origin)], vol.spacing)
    sess.render_frame()
    assert sess._extent_cache is not None
    cached = sess._extent_cache

    sess.update_grid(0, 0, data * 0.5)
    assert sess._extent_cache is cached     # same layout: no sync forced
    sess.render_frame()

    sess.update_data(0, [data], [np.asarray(vol.origin) + 1.0], vol.spacing)
    assert sess._extent_cache is None       # layout change invalidates


def test_scene_session_temporal_reseeds_on_regime_reentry(vol, tf):
    """A camera returning to a previously visited march regime must NOT
    reuse the threshold map frozen when it left (the grids kept updating):
    the entry is dropped and re-seeded, mirroring InSituSession."""
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.core.camera import Camera
    from scenery_insitu_tpu.runtime.scene_session import SceneSession

    cfg = FrameworkConfig().with_overrides(
        "vdi.max_supersegments=4", "vdi.adaptive_mode=temporal",
        "composite.max_output_supersegments=6", "composite.adaptive_iters=1",
        "slicer.engine=mxu", "slicer.matmul_dtype=f32",
        "runtime.dataset=procedural")
    sess = SceneSession(cfg)
    data = np.asarray(vol.data)
    sess.update_data(0, [data], [np.asarray(vol.origin)], vol.spacing)

    cam_z = Camera.create((0.1, 0.2, 3.0), fov_y_deg=50.0, near=0.3,
                          far=20.0)
    cam_x = Camera.create((3.0, 0.2, 0.1), fov_y_deg=50.0, near=0.3,
                          far=20.0)
    sess.camera = cam_z
    sess.render_frame()
    (key_z,) = list(sess._steps.thr)
    stale = sess._steps.thr[key_z]

    sess.camera = cam_x                      # leave the +z regime
    sess.render_frame()
    sess.update_grid(0, 0, data * 0.25)      # grids evolve meanwhile

    sess.camera = cam_z                      # return: must re-seed
    sess.render_frame()
    assert sess._steps.thr[key_z] is not stale


def test_scene_session_prewarm_regimes(vol, tf):
    """SceneSession.prewarm_regimes: precompiles per-regime steps for the
    current scene, leaves camera/threshold/frame state untouched, and the
    first real frame reuses the prewarmed step."""
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.scene_session import SceneSession

    cfg = FrameworkConfig().with_overrides(
        "vdi.max_supersegments=4", "vdi.adaptive_mode=temporal",
        "composite.max_output_supersegments=6", "composite.adaptive_iters=1",
        "slicer.engine=mxu", "slicer.matmul_dtype=f32",
        "runtime.dataset=procedural")
    sess = SceneSession(cfg)
    sess.update_data(0, [np.asarray(vol.data)], [np.asarray(vol.origin)],
                     vol.spacing)
    start = sess._slicer.choose_axis(sess.camera)
    eye0 = np.asarray(sess.camera.eye).copy()
    times = sess.prewarm_regimes(regimes=[start, (0, 1)])
    assert set(times) == {start, (0, 1)}
    assert len(sess._steps.steps) == 2
    assert sess._steps.thr == {}                 # invisible to the loop
    assert sess.frame_index == 0
    assert np.allclose(eye0, np.asarray(sess.camera.eye))
    p = sess.render_frame()
    assert np.isfinite(p["vdi_color"]).all()
    assert len(sess._steps.steps) == 2           # no third compile
