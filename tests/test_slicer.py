"""Tests for the MXU slice-march engine (ops/slicer.py): virtual-camera
geometry, cross-engine parity with the gather-path raycaster, VDI
generation equivalence, and edge cases (axes, signs, oblique cameras,
out-of-frustum volumes)."""

import jax.numpy as jnp
import numpy as np
import pytest

from scenery_insitu_tpu.config import SliceMarchConfig, VDIConfig
from scenery_insitu_tpu.core.camera import Camera, world_to_ndc
from scenery_insitu_tpu.core.transfer import TransferFunction, for_dataset
from scenery_insitu_tpu.core.volume import Volume, procedural_volume
from scenery_insitu_tpu.ops import slicer
from scenery_insitu_tpu.ops.raycast import raycast
from scenery_insitu_tpu.ops.vdi_gen import generate_vdi
from scenery_insitu_tpu.ops.vdi_render import render_vdi
from scenery_insitu_tpu.utils.image import psnr


F32 = SliceMarchConfig(matmul_dtype="f32", scale=1.5)


@pytest.fixture(scope="module")
def vol():
    return procedural_volume(48, kind="blobs", seed=3)


@pytest.fixture(scope="module")
def tf():
    return for_dataset("procedural")


def test_choose_axis():
    cam = Camera.create((0.0, 0.1, 3.0), target=(0.0, 0.0, 0.0))
    assert slicer.choose_axis(cam) == (2, -1)
    cam = Camera.create((-4.0, 0.1, 0.5), target=(0.0, 0.0, 0.0))
    assert slicer.choose_axis(cam) == (0, 1)
    cam = Camera.create((0.2, -3.0, 0.5), target=(0.0, 0.0, 0.0))
    assert slicer.choose_axis(cam) == (1, 1)


def test_axis_camera_grid_matches_projection(vol):
    """Grid point (j, i) must project through (proj, view) to the NDC of
    pixel center (i, j) — the invariant every metadata consumer relies on."""
    cam = Camera.create((0.4, 0.7, 2.5), fov_y_deg=45.0, near=0.3, far=10.0)
    spec = slicer.make_spec(cam, vol.data.shape, F32)
    axcam = slicer.make_axis_camera(vol, cam, spec)

    a, ua, va = spec.axis, spec.u_axis, spec.v_axis
    for (j, i) in [(0, 0), (spec.nj - 1, spec.ni - 1),
                   (spec.nj // 2, spec.ni // 3)]:
        p = np.zeros(3, np.float32)
        p[ua] = float(axcam.u_grid[i])
        p[va] = float(axcam.v_grid[j])
        p[a] = float(axcam.w0)
        ndc = np.asarray(world_to_ndc(jnp.asarray(p), axcam.view, axcam.proj))
        exp_x = (i + 0.5) / spec.ni * 2 - 1
        exp_y = 1 - (j + 0.5) / spec.nj * 2
        assert abs(ndc[0] - exp_x) < 1e-3, (i, j, ndc)
        assert abs(ndc[1] - exp_y) < 1e-3, (i, j, ndc)
        assert abs(ndc[2] - (-1.0)) < 1e-3  # ref plane == near plane


@pytest.mark.parametrize("eye", [(0.0, 0.3, 2.8), (2.6, 0.4, 0.9),
                                 (-2.4, -0.5, -1.1), (0.5, 2.7, -0.4)])
def test_raycast_parity_vs_gather(vol, tf, eye):
    """Cross-engine parity on all march axes/signs."""
    cam = Camera.create(eye, fov_y_deg=45.0, near=0.3, far=12.0)
    w, h = 96, 80
    ref = raycast(vol, tf, cam, w, h).image
    spec = slicer.make_spec(cam, vol.data.shape, F32)
    got = slicer.raycast_mxu(vol, tf, cam, w, h, spec).image
    q = psnr(ref, got)
    assert q > 28.0, f"PSNR {q:.1f} dB at eye {eye}"


def test_raycast_bf16_close(vol, tf):
    cam = Camera.create((0.0, 0.4, 2.8), fov_y_deg=45.0, near=0.3, far=12.0)
    w, h = 96, 80
    spec32 = slicer.make_spec(cam, vol.data.shape, F32)
    spec16 = slicer.make_spec(
        cam, vol.data.shape,
        SliceMarchConfig(matmul_dtype="bf16", scale=1.5))
    a = slicer.raycast_mxu(vol, tf, cam, w, h, spec32).image
    b = slicer.raycast_mxu(vol, tf, cam, w, h, spec16).image
    assert psnr(a, b) > 35.0


def test_homogeneous_transmittance(tf):
    """A homogeneous box must attenuate per Beer-Lambert regardless of the
    sampling schedule: checks the per-ray path-length opacity correction."""
    data = jnp.full((32, 32, 32), 0.5, jnp.float32)
    vol = Volume.centered(data, extent=1.0)
    tf_c = TransferFunction.ramp(0.0, 1.0, 0.4, "grays")
    cam = Camera.create((0.0, 0.0, 3.0), fov_y_deg=20.0, near=0.5, far=10.0)
    w = h = 32
    ref = raycast(vol, tf_c, cam, w, h, None).image
    spec = slicer.make_spec(cam, vol.data.shape, F32)
    got = slicer.raycast_mxu(vol, tf_c, cam, w, h, spec).image
    # compare center pixel alpha (full path through the cube)
    ra = float(ref[3, h // 2, w // 2])
    ga = float(got[3, h // 2, w // 2])
    assert abs(ra - ga) < 0.03, (ra, ga)


def test_volume_partially_outside(vol, tf):
    """Oblique close-up: part of the image misses the volume; no NaNs and
    misses keep the background."""
    cam = Camera.create((0.9, 0.8, 1.2), target=(0.4, 0.3, 0.0),
                        fov_y_deg=70.0, near=0.1, far=10.0)
    spec = slicer.make_spec(cam, vol.data.shape, F32)
    out = slicer.raycast_mxu(vol, tf, cam, 64, 64, spec,
                             background=(0.1, 0.2, 0.3, 1.0))
    img = np.asarray(out.image)
    assert np.isfinite(img).all()
    assert (img >= 0).all() and (img <= 1.0 + 1e-5).all()


def test_generate_vdi_mxu_renders_like_raycast(vol, tf):
    """VDI built by the slice march, decoded by the (unchanged) novel-view
    renderer at the real camera, must approximate the direct render."""
    cam = Camera.create((0.3, 0.5, 2.7), fov_y_deg=45.0, near=0.3, far=12.0)
    w, h = 80, 64
    spec = slicer.make_spec(cam, vol.data.shape, F32)
    vdi, meta, axcam = slicer.generate_vdi_mxu(
        vol, tf, cam, spec, VDIConfig(max_supersegments=12, adaptive_iters=4))
    img = render_vdi(vdi, meta, cam, w, h, steps=160)
    ref = raycast(vol, tf, cam, w, h).image
    q = psnr(ref, img)
    assert q > 22.0, f"PSNR {q:.1f} dB"


def test_generate_vdi_mxu_vs_gather_vdi(vol, tf):
    """Same-view decode of MXU VDI vs gather VDI (both through render_vdi
    at the true camera)."""
    cam = Camera.create((0.0, 0.4, 2.6), fov_y_deg=45.0, near=0.3, far=12.0)
    w, h = 64, 64
    cfg = VDIConfig(max_supersegments=12, adaptive_iters=4)
    spec = slicer.make_spec(cam, vol.data.shape, F32)
    vdi_m, meta_m, _ = slicer.generate_vdi_mxu(vol, tf, cam, spec, cfg)
    vdi_g, meta_g = generate_vdi(vol, tf, cam, w, h, cfg, max_steps=160)
    img_m = render_vdi(vdi_m, meta_m, cam, w, h, steps=160)
    img_g = render_vdi(vdi_g, meta_g, cam, w, h, steps=160)
    q = psnr(img_g, img_m)
    assert q > 22.0, f"PSNR {q:.1f} dB"


def test_vdi_depths_ordered(vol, tf):
    cam = Camera.create((0.0, 0.4, 2.6), fov_y_deg=45.0, near=0.3, far=12.0)
    spec = slicer.make_spec(cam, vol.data.shape, F32)
    vdi, meta, _ = slicer.generate_vdi_mxu(
        vol, tf, cam, spec, VDIConfig(max_supersegments=8, adaptive_iters=3))
    start = np.asarray(vdi.depth[:, 0])
    end = np.asarray(vdi.depth[:, 1])
    live = np.asarray(vdi.color[:, 3]) > 0
    assert (end[live] >= start[live]).all()
    # consecutive live slots are depth-sorted
    k = vdi.k
    for s in range(k - 1):
        both = live[s] & live[s + 1]
        assert (start[s + 1][both] >= end[s][both] - 1e-4).all()


def test_warp_roundtrip_identity(vol):
    """Warping a smooth intermediate image to a camera looking straight
    down the axis reproduces the image structure (low-frequency check)."""
    cam = Camera.create((0.0, 0.0, 3.0), fov_y_deg=40.0, near=0.5, far=10.0)
    spec = slicer.make_spec(cam, vol.data.shape, F32)
    axcam = slicer.make_axis_camera(vol, cam, spec)
    jj, ii = jnp.meshgrid(jnp.linspace(0, 1, spec.nj),
                          jnp.linspace(0, 1, spec.ni), indexing="ij")
    img = jnp.stack([ii, jj, ii * jj, jnp.ones_like(ii)])
    out = slicer.warp_to_camera(img, axcam, spec, cam, 48, 48,
                                background=None)
    o = np.asarray(out)
    assert np.isfinite(o).all()
    # u increases to the right, v decreases downward in both spaces
    assert o[0, 24, 40] > o[0, 24, 8]
    assert o[1, 40, 24] > o[1, 8, 24]


# ------------------------------------------------- occupancy acceleration


def test_occupancy_skip_is_exact(vol, tf):
    """Empty-space skipping must not change a single output value: the
    skipped branch feeds one explicit empty sample, reproducing the gap
    semantics of the full march bit-for-bit."""
    cam = Camera.create((0.3, 0.5, 2.8), fov_y_deg=45.0, near=0.3, far=10.0)
    spec_on = slicer.make_spec(cam, vol.data.shape, F32)
    spec_off = slicer.make_spec(
        cam, vol.data.shape,
        SliceMarchConfig(matmul_dtype="f32", scale=1.5, skip_empty=False))
    cfg = VDIConfig(max_supersegments=6, adaptive_iters=2)
    vdi_on, _, _ = slicer.generate_vdi_mxu(vol, tf, cam, spec_on, cfg)
    vdi_off, _, _ = slicer.generate_vdi_mxu(vol, tf, cam, spec_off, cfg)
    np.testing.assert_allclose(np.asarray(vdi_on.color),
                               np.asarray(vdi_off.color), atol=1e-6)
    d_on = np.nan_to_num(np.asarray(vdi_on.depth), posinf=1e9)
    d_off = np.nan_to_num(np.asarray(vdi_off.depth), posinf=1e9)
    np.testing.assert_allclose(d_on, d_off, atol=1e-5)


def test_occupancy_flags_conservative(tf):
    """Every chunk flagged empty must truly contribute zero alpha — checked
    in MARCH order (chunk_occupancy walks the storage-order layout front
    to back by the sign), on an asymmetric band so a flip-indexing
    regression cannot pass."""
    data = jnp.zeros((64, 16, 16), jnp.float32)
    data = data.at[8:24].set(0.9)          # asymmetric occupied band
    v = Volume.centered(data, extent=2.0)
    cam = Camera.create((0.0, 0.2, 3.0), fov_y_deg=45.0)
    spec = slicer.make_spec(cam, v.data.shape, F32)
    assert spec.axis == 2                  # the camera this test assumes
    occ = np.asarray(slicer.chunk_occupancy(v, tf, spec))
    assert occ.sum() < occ.size            # something was skippable
    volp = np.asarray(slicer.permute_volume(v, spec))[::spec.sign]
    c = spec.chunk
    for ci in range(occ.size):
        band = volp[ci * c:(ci + 1) * c]
        if band.size and band.max() > 0.5:
            assert occ[ci], f"occupied chunk {ci} flagged empty"
        if band.size and band.max() < 1e-6:
            assert not occ[ci], f"empty chunk {ci} flagged occupied"


def test_render_slices_early_stop_exact(tf):
    """Saturation early-out must not change the image (gated pixels stop
    accumulating anyway)."""
    data = jnp.full((48, 48, 48), 0.95, jnp.float32)   # dense, saturates fast
    v = Volume.centered(data, extent=2.0)
    cam = Camera.create((0.0, 0.1, 3.0), fov_y_deg=45.0)
    spec = slicer.make_spec(cam, v.data.shape, F32)
    axcam = slicer.make_axis_camera(v, cam, spec)
    out_fast = slicer.render_slices(v, tf, axcam, spec)
    # reference: no occupancy, no early stop
    spec_off = slicer.make_spec(
        cam, v.data.shape,
        SliceMarchConfig(matmul_dtype="f32", scale=1.5, skip_empty=False))
    axcam2 = slicer.make_axis_camera(v, cam, spec_off)

    def consume(carry, rgba, t0, t1):
        acc, first_t = carry
        for i in range(rgba.shape[0]):
            gate = (acc[3] < 0.999).astype(jnp.float32)
            src = rgba[i] * gate[None]
            acc = acc + (1.0 - acc[3:4]) * src
            first_t = jnp.where((first_t == jnp.inf) & (src[3] > 1e-4),
                                t0[i], first_t)
        return acc, first_t

    acc0 = jnp.zeros((4, spec_off.nj, spec_off.ni), jnp.float32)
    ft0 = jnp.full((spec_off.nj, spec_off.ni), jnp.inf, jnp.float32)
    acc, _ = slicer.slice_march(v, tf, axcam2, spec_off, consume, (acc0, ft0))
    np.testing.assert_allclose(np.asarray(out_fast.image), np.asarray(acc),
                               atol=1e-5)


def test_hittable_mask_conservative():
    """Every pixel that accumulates any alpha must be flagged hittable, and
    the mask must exclude some frustum-margin pixels (it exists so that
    whole-grid predicates can ignore rays that miss the volume)."""
    data = jnp.full((48, 48, 48), 0.95, jnp.float32)
    tf = TransferFunction.ramp(0.0, 0.5, 1.0)
    v = Volume.centered(data, extent=2.0)
    cam = Camera.create((0.0, 0.1, 3.0), fov_y_deg=45.0)
    spec = slicer.make_spec(cam, v.data.shape, F32)
    axcam = slicer.make_axis_camera(v, cam, spec)
    out = slicer.render_slices(v, tf, axcam, spec)
    miss = ~np.asarray(slicer.hittable_mask(v, axcam, spec))
    hit = np.asarray(out.image[3]) > 1e-4
    assert not (hit & miss).any()
    assert miss.any()                      # margins are excluded


def test_slice_march_early_stop_mechanism():
    """The generic early_stop hook must actually skip chunks: a consumer
    counting processed samples sees fewer once the predicate turns true,
    while a permanently-false predicate reproduces the full march."""
    data = jnp.full((64, 16, 16), 0.5, jnp.float32)
    tf = TransferFunction.ramp(0.0, 0.5, 1.0)
    v = Volume.centered(data, extent=2.0)
    cam = Camera.create((0.0, 0.0, 3.0), fov_y_deg=45.0)
    spec = slicer.make_spec(cam, v.data.shape, F32)
    axcam = slicer.make_axis_camera(v, cam, spec)

    def consume(carry, rgba, t0, t1):
        return carry + rgba.shape[0]       # samples seen

    full = slicer.slice_march(v, tf, axcam, spec, consume,
                              jnp.int32(0),
                              early_stop=lambda c: jnp.bool_(False))
    stopped = slicer.slice_march(v, tf, axcam, spec, consume,
                                 jnp.int32(0),
                                 early_stop=lambda c: c >= spec.chunk)
    assert int(full) > int(stopped)
    # after the first chunk the predicate is true: one full chunk + one
    # empty sample per remaining chunk
    nchunks = int(full) // spec.chunk
    assert int(stopped) == spec.chunk + (nchunks - 1)


def test_update_threshold_controller():
    """One bisection step per frame: over-cap moves up inside the bracket,
    in-band holds (and tightens hi), under-band moves down; the bracket
    makes a knife-edge pixel converge instead of oscillating."""
    from scenery_insitu_tpu.ops import supersegments as ss

    thr = jnp.array([[0.1, 0.1, 0.1]], jnp.float32)
    st = ss.init_threshold_state(thr, thr_min=1e-3, thr_max=2.0)
    cnt = jnp.array([[40, 10, 6]], jnp.int32)   # K=10, delta=0.15
    new = ss.update_threshold(st, cnt, 10, delta=0.15,
                              thr_min=1e-3, thr_max=2.0)
    t = np.asarray(new.thr)
    assert t[0, 0] == pytest.approx(0.5 * (0.1 + 2.0))   # bisect toward hi
    assert t[0, 1] == pytest.approx(0.1)                 # in band: hold
    assert t[0, 2] == pytest.approx(0.5 * (0.1 + 1e-3))  # bisect toward lo
    assert float(new.lo[0, 0]) == pytest.approx(0.1 * 0.9)  # decayed bound
    assert float(new.hi[0, 1]) == pytest.approx(0.1 / 0.9)

    # knife-edge convergence: count jumps 14 -> 4 across thr*, a plain
    # multiplicative controller oscillates forever; the bracket pins it
    def count_of(t):
        return jnp.where(t < 0.31, 14, 4).astype(jnp.int32)

    st = ss.init_threshold_state(jnp.full((1, 1), 0.01, jnp.float32))
    over_frames = 0
    for _ in range(30):
        c = count_of(st.thr)
        over_frames += int((c > 10).sum())
        st = ss.update_threshold(st, c, 10)
    # after convergence the threshold sits on the fitting side of the edge
    final_over = int((count_of(st.thr) > 10).sum())
    assert final_over == 0
    assert over_frames < 10   # transient only, not persistent oscillation


def test_temporal_mode_matches_histogram_quality(vol, tf):
    """After the seeded march, temporal one-march frames render like the
    per-frame histogram mode, and the carried counts sit in/below band."""
    from scenery_insitu_tpu.ops import supersegments as ss

    cam = Camera.create((0.3, 0.5, 2.7), fov_y_deg=45.0, near=0.3, far=12.0)
    w, h = 80, 64
    k = 12
    spec = slicer.make_spec(cam, vol.data.shape, F32)
    cfg_t = VDIConfig(max_supersegments=k, adaptive_mode="temporal")
    cfg_h = VDIConfig(max_supersegments=k, adaptive_mode="histogram")

    thr = slicer.initial_threshold(vol, tf, cam, spec, cfg_t)
    assert thr.thr.shape == (spec.nj, spec.ni)
    for _ in range(6):
        vdi_t, meta_t, _, thr = slicer.generate_vdi_mxu_temporal(
            vol, tf, cam, spec, thr, cfg_t)
    vdi_h, meta_h, _ = slicer.generate_vdi_mxu(vol, tf, cam, spec, cfg_h)

    img_t = render_vdi(vdi_t, meta_t, cam, w, h, steps=160)
    img_h = render_vdi(vdi_h, meta_h, cam, w, h, steps=160)
    q = psnr(img_h, img_t)
    assert q > 25.0, f"PSNR {q:.1f} dB"

    # steady state: the TRUE (uncapped) segment count at the converged
    # threshold stays within the cap for (nearly) every pixel — measured
    # by an independent counting march, not the capped writer state
    axcam = slicer.make_axis_camera(vol, cam, spec)

    def consume(cst, rgba, t0, t1):
        for i in range(rgba.shape[0]):
            cst = ss.push_count(cst, thr.thr, rgba[i])
        return cst

    counts = np.asarray(slicer.slice_march(
        vol, tf, axcam, spec, consume,
        ss.init_count(spec.nj, spec.ni)).count)
    frac_over = (counts > k).mean()
    assert frac_over < 0.01, f"{frac_over:.3%} of pixels over cap"


def test_generate_vdi_mxu_rejects_temporal_mode(vol, tf):
    cam = Camera.create((0.0, 0.4, 2.6), fov_y_deg=45.0, near=0.3, far=12.0)
    spec = slicer.make_spec(cam, vol.data.shape, F32)
    with pytest.raises(ValueError, match="temporal"):
        slicer.generate_vdi_mxu(
            vol, tf, cam, spec, VDIConfig(adaptive_mode="temporal"))


def test_vtile_occupancy_gating_is_exact(tf):
    """In-plane occupancy tiles (spec.vtiles > 0) must change NOTHING in
    the output — gated row blocks are provably zero-alpha, so tiled and
    untiled renders and VDIs must match to the bit. Sparse corner blob:
    most (chunk, v-tile) cells empty, so the gate genuinely fires."""
    data = np.zeros((48, 48, 48), np.float32)
    data[4:16, 6:18, 8:20] = 0.8            # one blob near a corner
    svol = Volume.centered(jnp.asarray(data), extent=2.0)
    cam = Camera.create((0.3, 0.4, 2.8), fov_y_deg=45.0, near=0.3,
                        far=10.0)
    base = SliceMarchConfig(matmul_dtype="f32", scale=1.25)
    tiled = SliceMarchConfig(matmul_dtype="f32", scale=1.25,
                             occupancy_vtiles=6)
    spec0 = slicer.make_spec(cam, svol.data.shape, base)
    spec1 = slicer.make_spec(cam, svol.data.shape, tiled)
    assert spec1.vtiles == 6

    # the occupancy structure really is tile-granular and really sparse
    occ = slicer.occupancy_for(svol, tf, spec1)
    assert isinstance(occ, tuple)
    tile_frac = float(np.asarray(occ[1]).mean())
    assert tile_frac < 0.5, f"blob scene not sparse? {tile_frac}"

    img0 = slicer.raycast_mxu(svol, tf, cam, 64, 48, spec0)
    img1 = slicer.raycast_mxu(svol, tf, cam, 64, 48, spec1)
    np.testing.assert_array_equal(np.asarray(img1.image),
                                  np.asarray(img0.image))
    np.testing.assert_array_equal(np.asarray(img1.depth),
                                  np.asarray(img0.depth))

    cfg = VDIConfig(max_supersegments=6, adaptive_mode="histogram",
                    histogram_bins=8)
    vdi0, _, _ = slicer.generate_vdi_mxu(svol, tf, cam, spec0, cfg)
    vdi1, _, _ = slicer.generate_vdi_mxu(svol, tf, cam, spec1, cfg)
    np.testing.assert_array_equal(np.asarray(vdi1.color),
                                  np.asarray(vdi0.color))
    np.testing.assert_array_equal(np.asarray(vdi1.depth),
                                  np.asarray(vdi0.depth))


def test_vtile_apron_catches_bandpass_tf():
    """The adversarial case for banded occupancy: two value plateaus
    meeting exactly AT a tile boundary, and a band-pass TF whose alpha
    peak lies strictly between the plateau values. Only interpolated
    rows near the boundary produce visible alpha; apron-less bands would
    both claim 'empty' and the gated march would drop the interface."""
    from scenery_insitu_tpu.core.transfer import TransferFunction

    n = 48
    data = np.zeros((n, n, n), np.float32)
    data[:, n // 2:, :] = 1.0               # plateau split along v (y)
    svol = Volume.centered(jnp.asarray(data), extent=2.0)
    bp_tf = TransferFunction.from_polylines(
        [(0.0, 0.0), (0.5, 0.9), (1.0, 0.0)],      # peak between plateaus
        np.array([0.0, 1.0]),
        np.array([[1.0, 0.5, 0.1], [1.0, 0.5, 0.1]], np.float32))
    cam = Camera.create((0.1, 0.2, 2.9), fov_y_deg=45.0, near=0.3,
                        far=10.0)
    base = SliceMarchConfig(matmul_dtype="f32", scale=1.25)
    tiled = SliceMarchConfig(matmul_dtype="f32", scale=1.25,
                             occupancy_vtiles=6)   # boundary ON a tile edge
    spec0 = slicer.make_spec(cam, svol.data.shape, base)
    spec1 = slicer.make_spec(cam, svol.data.shape, tiled)
    img0 = slicer.raycast_mxu(svol, bp_tf, cam, 64, 48, spec0)
    img1 = slicer.raycast_mxu(svol, bp_tf, cam, 64, 48, spec1)
    # the interface IS visible (nonzero alpha) and the tiled render
    # reproduces it exactly
    assert float(np.asarray(img0.image)[3].max()) > 0.2
    np.testing.assert_array_equal(np.asarray(img1.image),
                                  np.asarray(img0.image))


def test_vtile_clamp_on_small_volumes():
    """An oversized occupancy_vtiles request degrades to coarser tiles
    instead of zero-width bands blowing up at trace time."""
    cam = Camera.create((0.0, 0.1, 2.8), fov_y_deg=45.0, near=0.3,
                        far=10.0)
    spec = slicer.make_spec(cam, (16, 16, 16),
                            SliceMarchConfig(matmul_dtype="f32", scale=1.0,
                                             occupancy_vtiles=64))
    assert 0 < spec.vtiles <= 8


def test_plain_fold_matches_sequential_loop(vol, tf):
    """The chunk-parallel plain alpha-under (with its prefix-gate
    saturation semantics) must reproduce the per-slice sequential
    accumulator exactly — including first-hit depths and gate freezing."""
    cam = Camera.create((0.2, 0.5, 2.9), fov_y_deg=45.0, near=0.3,
                        far=10.0)
    spec = slicer.make_spec(cam, vol.data.shape,
                            SliceMarchConfig(matmul_dtype="f32", scale=1.0))
    axcam = slicer.make_axis_camera(vol, cam, spec)
    # aggressive threshold so the gate actually fires mid-volume
    out = slicer.render_slices(vol, tf, axcam, spec,
                               early_exit_alpha=0.6)

    def consume_seq(carry, rgba, t0, t1):
        acc, first_t = carry
        for i in range(rgba.shape[0]):
            gate = (acc[3] < 0.6).astype(jnp.float32)
            src = rgba[i] * gate[None]
            acc = acc + (1.0 - acc[3:4]) * src
            first_t = jnp.where((first_t == jnp.inf) & (src[3] > 1e-4),
                                t0[i], first_t)
        return acc, first_t

    acc0 = jnp.zeros((4, spec.nj, spec.ni), jnp.float32)
    t0 = jnp.full((spec.nj, spec.ni), jnp.inf, jnp.float32)
    occ = slicer.occupancy_for(vol, tf, spec)
    acc, ft = slicer.slice_march(vol, tf, axcam, spec, consume_seq,
                                 (acc0, t0), occupancy=occ)
    # a pixel whose accumulated alpha lands within ~1 ulp of the gate
    # threshold may round the gate differently between the two forms and
    # shift by one full sample — measure-zero, so allow a vanishing
    # mismatch fraction instead of exact equality
    img_ok = np.isclose(np.asarray(out.image), np.asarray(acc),
                        rtol=1e-5, atol=1e-6)
    assert img_ok.mean() > 0.999, f"mismatch {1 - img_ok.mean():.2%}"
    d0, d1 = np.asarray(out.depth), np.asarray(ft)
    depth_ok = (d0 == d1) | np.isclose(d0, d1)
    assert depth_ok.mean() > 0.999
