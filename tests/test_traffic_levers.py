"""Tests for the ISSUE-1 HBM-traffic levers: the time-fused 2D-blocked
sim stencil's guard rails, the bf16 marched-volume path, and the
pallas_seg argument-form/probe fixes that rode along (ADVICE.md round
5)."""

import jax.numpy as jnp
import numpy as np
import pytest

from scenery_insitu_tpu.config import SliceMarchConfig, VDIConfig
from scenery_insitu_tpu.core.camera import Camera
from scenery_insitu_tpu.core.transfer import for_dataset
from scenery_insitu_tpu.core.volume import Volume
from scenery_insitu_tpu.ops import slicer
from scenery_insitu_tpu.parallel.mesh import make_mesh
from scenery_insitu_tpu.sim import grayscott as gs


# ------------------------------------------------- stencil guard rails


def test_step_pallas2d_rejects_bad_tile():
    """An explicit (tz, th) off the T | tz | D and T | th | H lattice
    must raise instead of floor-dividing the grid and silently leaving
    output tiles unwritten."""
    from scenery_insitu_tpu.sim import pallas_stencil as ps

    st = gs.GrayScott.init((16, 32, 128), n_seeds=1)
    pvec = jnp.stack([st.params.f, st.params.k, st.params.du,
                      st.params.dv, st.params.dt])
    for tz, th in ((12, 32), (8, 24), (6, 32), (8, 12)):
        with pytest.raises(ValueError, match="violates"):
            ps.step_pallas2d(st.u, st.v, pvec, 4, interpret=True,
                             tz=tz, th=th)
    with pytest.raises(ValueError, match="both tz and th"):
        ps.step_pallas2d(st.u, st.v, pvec, 4, interpret=True, tz=8)


def test_step_pallas_rejects_bad_tz():
    from scenery_insitu_tpu.sim import pallas_stencil as ps

    st = gs.GrayScott.init((16, 16, 128), n_seeds=1)
    pvec = jnp.stack([st.params.f, st.params.k, st.params.du,
                      st.params.dv, st.params.dt])
    for tz in (12, 6):   # 12 does not divide 16; 6 % t_steps(4) != 0
        with pytest.raises(ValueError, match="violates"):
            ps.step_pallas(st.u, st.v, pvec, 4, interpret=True, tz=tz)


def test_modeled_sim_traffic_fusion_wins():
    """The schedule-model traffic of a fused 512^3 10-step advance must
    undercut the roll floor by >= 2x (the PERF.md lever-1 claim the
    bench's traffic-model fallback now encodes)."""
    from scenery_insitu_tpu.sim import pallas_stencil as ps

    shape = (512, 512, 512)
    fused = ps.modeled_sim_traffic(shape, 10, fused=True)
    rolled = ps.modeled_sim_traffic(shape, 10, fused=False)
    assert rolled == 10 * 2 * 2 * 4.0 * 512 ** 3
    assert fused < rolled / 2.0


# ------------------------------------------------- bf16 marched volume


def _small_vol(grid=16, seed_steps=30):
    st = gs.multi_step(gs.GrayScott.init((grid,) * 3, n_seeds=2),
                       seed_steps)
    return Volume.centered(st.field, extent=2.0)


def test_render_dtype_threads_from_config():
    cfg = SliceMarchConfig(render_dtype="bf16", matmul_dtype="f32")
    spec = slicer.make_spec(Camera.create((0.0, 0.2, 2.5)), (16, 16, 16),
                            cfg)
    assert spec.render_dtype == "bf16"
    vol = _small_vol()
    assert slicer.permute_volume(vol, spec).dtype == jnp.bfloat16
    f32spec = slicer.make_spec(Camera.create((0.0, 0.2, 2.5)),
                               (16, 16, 16), SliceMarchConfig())
    assert slicer.permute_volume(vol, f32spec).dtype == jnp.float32
    with pytest.raises(ValueError, match="render_dtype"):
        SliceMarchConfig(render_dtype="f16")


def test_bf16_march_matches_f32():
    """The bf16 marched-volume copy must reproduce the f32 VDI within
    storage-rounding tolerance (accumulation stays f32 — only the volume
    values themselves are rounded once)."""
    vol = _small_vol()
    tf = for_dataset("gray_scott")
    cam = Camera.create((0.0, 0.3, 2.5), fov_y_deg=50.0, near=0.3,
                        far=20.0)
    vdi_cfg = VDIConfig(max_supersegments=6, adaptive_iters=2)
    outs = {}
    for rdt in ("f32", "bf16"):
        cfg = SliceMarchConfig(scale=1.0, matmul_dtype="f32",
                               render_dtype=rdt)
        spec = slicer.make_spec(cam, vol.data.shape, cfg)
        vdi, _, _ = slicer.generate_vdi_mxu(vol, tf, cam, spec, vdi_cfg)
        outs[rdt] = np.asarray(vdi.color)
    assert np.isfinite(outs["bf16"]).all()
    # bf16 has ~3 decimal digits; color channels are O(1)
    np.testing.assert_allclose(outs["bf16"], outs["f32"], atol=0.05)
    # and the paths must actually differ (the cast really happened)
    assert np.abs(outs["bf16"] - outs["f32"]).max() > 0.0


def test_bf16_render_slices_matches_f32():
    vol = _small_vol()
    tf = for_dataset("gray_scott")
    cam = Camera.create((0.2, 0.4, 2.5), fov_y_deg=50.0, near=0.3,
                        far=20.0)
    outs = {}
    for rdt in ("f32", "bf16"):
        cfg = SliceMarchConfig(scale=1.0, matmul_dtype="f32",
                               render_dtype=rdt)
        spec = slicer.make_spec(cam, vol.data.shape, cfg)
        axcam = slicer.make_axis_camera(vol, cam, spec)
        out = slicer.render_slices(vol, tf, axcam, spec)
        outs[rdt] = np.asarray(out.image)
    np.testing.assert_allclose(outs["bf16"], outs["f32"], atol=0.05)


def test_bf16_distributed_matches_f32():
    """The distributed rank-slab path casts before the halo exchange;
    the composited frame must stay within bf16 tolerance of f32."""
    from scenery_insitu_tpu.parallel.pipeline import (
        distributed_vdi_step_mxu, shard_volume)

    mesh = make_mesh(4)
    st = gs.multi_step(gs.GrayScott.init((16, 16, 16), n_seeds=2), 30)
    tf = for_dataset("gray_scott")
    cam = Camera.create((0.0, 0.3, 2.5), fov_y_deg=50.0, near=0.3,
                        far=20.0)
    origin = jnp.array([-1.0, -1.0, -1.0], jnp.float32)
    spacing = jnp.full((3,), 2.0 / 16, jnp.float32)
    vdi_cfg = VDIConfig(max_supersegments=6, adaptive_iters=2)
    outs = {}
    for rdt in ("f32", "bf16"):
        cfg = SliceMarchConfig(scale=1.0, matmul_dtype="f32",
                               render_dtype=rdt)
        spec = slicer.make_spec(cam, (16, 16, 16), cfg, multiple_of=4)
        step = distributed_vdi_step_mxu(mesh, tf, spec, vdi_cfg)
        vdi, _ = step(shard_volume(st.field, mesh), origin, spacing, cam)
        outs[rdt] = np.asarray(vdi.color)
    np.testing.assert_allclose(outs["bf16"], outs["f32"], atol=0.05)
