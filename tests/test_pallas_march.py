"""Parity tests for the fused Pallas march fold (ops/pallas_march.py):
the VMEM pixel-strip schedule must match the XLA lax.scan fold it
replaces to FMA-fusion tolerance (integer counts exactly) — same ops.supersegments state machine, two schedules
(≅ the reference's fused VDIGenerator.comp + AccumulateVDI.comp kernel
vs its own per-stage decomposition)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scenery_insitu_tpu.config import SliceMarchConfig, VDIConfig
from scenery_insitu_tpu.core.camera import Camera
from scenery_insitu_tpu.core.transfer import for_dataset
from scenery_insitu_tpu.core.volume import (Volume,
                                             procedural_volume)
from scenery_insitu_tpu.ops import pallas_march as pm
from scenery_insitu_tpu.ops import slicer
from scenery_insitu_tpu.ops import supersegments as ss

XLA = SliceMarchConfig(matmul_dtype="f32", scale=1.5, fold="xla")
PALLAS = SliceMarchConfig(matmul_dtype="f32", scale=1.5, fold="pallas")


@pytest.fixture(scope="module")
def vol():
    return procedural_volume(40, kind="blobs", seed=7)


@pytest.fixture(scope="module")
def tf():
    return for_dataset("procedural")


def _stream(key, n, h, w, empty_runs=True):
    """Random depth-ordered item stream with empties and near-duplicates —
    exercises close-on-gap, close-on-diff and merge-overflow paths."""
    kr, ka, kd = jax.random.split(key, 3)
    rgb = jax.random.uniform(kr, (n, 3, h, w))
    alpha = jax.random.uniform(ka, (n, 1, h, w))
    if empty_runs:
        # ~40% empty items, in runs
        gate = jax.random.uniform(kd, (n, 1, h, w)) > 0.4
        alpha = alpha * gate
    rgba = jnp.concatenate([rgb * alpha, alpha], axis=1)
    t0 = jnp.cumsum(jnp.full((n, h, w), 0.1), axis=0)
    return rgba, t0, t0 + 0.1


def _fold_xla(rgba, t0, t1, thr, max_k):
    st = ss.init_state(max_k, rgba.shape[2], rgba.shape[3])
    cst = ss.init_count(rgba.shape[2], rgba.shape[3])
    for i in range(rgba.shape[0]):
        st = ss.push(st, max_k, thr, rgba[i], t0[i], t1[i])
        cst = ss.push_count(cst, thr, rgba[i])
    return st, cst


def test_fold_chunk_matches_sequential_push():
    h, w = 16, 40                       # w deliberately NOT 128-aligned
    max_k = 5
    rgba, t0, t1 = _stream(jax.random.PRNGKey(0), 12, h, w)
    thr = jnp.full((h, w), 0.35, jnp.float32)

    st_ref, cst_ref = _fold_xla(rgba, t0, t1, thr, max_k)
    c_ref, d_ref = ss.finalize(st_ref)

    packed = pm.init_packed(max_k, h, w)
    count = jnp.zeros((h, w), jnp.int32)
    # two chunk calls — state must round-trip exactly between them
    packed, count = pm.fold_chunk(packed, rgba[:7], t0[:7], t1[:7], thr,
                                  max_k=max_k, count=count)
    packed, count = pm.fold_chunk(packed, rgba[7:], t0[7:], t1[7:], thr,
                                  max_k=max_k, count=count)
    c_p, d_p = ss.finalize(pm.unpack_state(packed))

    np.testing.assert_allclose(np.asarray(c_p), np.asarray(c_ref),
                               rtol=2e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(d_p), np.asarray(d_ref),
                               rtol=2e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(count),
                                  np.asarray(cst_ref.count))


def test_fold_chunk_without_count():
    h, w = 8, 33
    max_k = 4
    rgba, t0, t1 = _stream(jax.random.PRNGKey(3), 9, h, w)
    thr = jnp.float32(0.2)              # scalar threshold broadcast

    st_ref, _ = _fold_xla(rgba, t0, t1, jnp.full((h, w), 0.2), max_k)
    packed = pm.fold_chunk(pm.init_packed(max_k, h, w), rgba, t0, t1, thr,
                           max_k=max_k)
    c_p, d_p = ss.finalize(pm.unpack_state(packed))
    c_ref, d_ref = ss.finalize(st_ref)
    np.testing.assert_allclose(np.asarray(c_p), np.asarray(c_ref),
                               rtol=2e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(d_p), np.asarray(d_ref),
                               rtol=2e-6, atol=1e-6)


def test_count_multi_matches_push_count():
    h, w = 16, 24
    bins = 6
    rgba, t0, t1 = _stream(jax.random.PRNGKey(5), 10, h, w)
    tvec = ss.threshold_candidates(bins, 2.0)

    st = ss.init_count_multi(bins, h, w)
    for i in range(rgba.shape[0]):
        st = ss.push_count(st, tvec[:, None, None], rgba[i])

    carry = pm.init_count_multi_packed(bins, h, w)
    carry = pm.count_multi_chunk(carry, rgba[:4], np.asarray(tvec))
    carry = pm.count_multi_chunk(carry, rgba[4:], np.asarray(tvec))
    np.testing.assert_array_equal(np.asarray(carry[0]),
                                  np.asarray(st.count))


def test_generate_vdi_mxu_fold_parity(vol, tf):
    """Whole-march parity: fold='pallas' must reproduce fold='xla' exactly
    (histogram adaptive mode — both the counting and write marches fused)."""
    cam = Camera.create((0.25, 0.5, 2.6), fov_y_deg=45.0, near=0.3, far=10.0)
    cfg = VDIConfig(max_supersegments=6, adaptive_mode="histogram",
                    histogram_bins=8)
    spec_x = slicer.make_spec(cam, vol.data.shape, XLA)
    spec_p = slicer.make_spec(cam, vol.data.shape, PALLAS)
    assert spec_p.fold == "pallas" and spec_x.fold == "xla"

    vdi_x, meta_x, _ = slicer.generate_vdi_mxu(vol, tf, cam, spec_x, cfg)
    vdi_p, meta_p, _ = slicer.generate_vdi_mxu(vol, tf, cam, spec_p, cfg)
    np.testing.assert_allclose(np.asarray(vdi_p.color),
                               np.asarray(vdi_x.color), rtol=2e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(vdi_p.depth),
                               np.asarray(vdi_x.depth), rtol=2e-6, atol=1e-6)


def test_temporal_fold_parity(vol, tf):
    """Temporal mode: fused write+count kernel must produce the same VDI
    AND the same next-frame threshold state as the XLA side-by-side fold,
    across several carried frames."""
    cam = Camera.create((0.0, 0.4, 2.8), fov_y_deg=45.0, near=0.3, far=10.0)
    cfg = VDIConfig(max_supersegments=6, adaptive_mode="temporal")
    spec_x = slicer.make_spec(cam, vol.data.shape, XLA)
    spec_p = slicer.make_spec(cam, vol.data.shape, PALLAS)

    thr_x = slicer.initial_threshold(vol, tf, cam, spec_x, cfg)
    thr_p = slicer.initial_threshold(vol, tf, cam, spec_p, cfg)
    np.testing.assert_allclose(np.asarray(thr_p.thr),
                               np.asarray(thr_x.thr), rtol=2e-6, atol=1e-6)

    for _ in range(3):
        vdi_x, _, _, thr_x = slicer.generate_vdi_mxu_temporal(
            vol, tf, cam, spec_x, thr_x, cfg)
        vdi_p, _, _, thr_p = slicer.generate_vdi_mxu_temporal(
            vol, tf, cam, spec_p, thr_p, cfg)
        np.testing.assert_allclose(np.asarray(vdi_p.color),
                               np.asarray(vdi_x.color), rtol=2e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(vdi_p.depth),
                               np.asarray(vdi_x.depth), rtol=2e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(thr_p.thr),
                               np.asarray(thr_x.thr), rtol=2e-6, atol=1e-6)


def test_fold_parity_under_jit(vol, tf):
    """The production call shape: the whole generate step jitted, pallas
    fold inside — must still match and must be jit-stable."""
    cam = Camera.create((0.1, 0.5, 2.7), fov_y_deg=45.0, near=0.3, far=10.0)
    cfg = VDIConfig(max_supersegments=5, adaptive_mode="histogram",
                    histogram_bins=8)
    spec_p = slicer.make_spec(cam, vol.data.shape, PALLAS)
    spec_x = slicer.make_spec(cam, vol.data.shape, XLA)

    @jax.jit
    def gen_p(data):
        v = type(vol)(data, vol.origin, vol.spacing)
        vdi, _, _ = slicer.generate_vdi_mxu(v, tf, cam, spec_p, cfg)
        return vdi.color, vdi.depth

    @jax.jit
    def gen_x(data):
        v = type(vol)(data, vol.origin, vol.spacing)
        vdi, _, _ = slicer.generate_vdi_mxu(v, tf, cam, spec_x, cfg)
        return vdi.color, vdi.depth

    cp, dp = gen_p(vol.data)
    cx, dx = gen_x(vol.data)
    np.testing.assert_allclose(np.asarray(cp), np.asarray(cx),
                               rtol=2e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dp), np.asarray(dx),
                               rtol=2e-6, atol=1e-6)


def test_auto_fold_resolution(monkeypatch):
    """"auto" resolves by backend NAME — the XLA fold off-TPU
    (interpret-mode pallas is slow; conftest pins the cpu backend), the
    seg kernel that shades the march's value plane itself on TPU (since
    PR 46; `slicer.fold_schedule` gives a march without a scalar volume
    or a concrete TF its shaded feed) with no compile probe in between
    (a Mosaic refusal raises at compile time) — and an explicit fold
    choice is always honored."""
    assert jax.default_backend() == "cpu"        # conftest invariant
    cam = Camera.create((0.0, 0.4, 2.8))
    spec = slicer.make_spec(cam, (16, 16, 16), SliceMarchConfig())
    assert spec.fold == "xla"
    spec_p = slicer.make_spec(cam, (16, 16, 16), PALLAS)
    assert spec_p.fold == "pallas"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spec_t = slicer.make_spec(cam, (16, 16, 16), SliceMarchConfig())
    assert spec_t.fold == "pallas_fused"


def test_skip_chunks_execute_through_pallas_fold(tf):
    """Occupancy skipping EXECUTES the C=1 empty-sample branch through the
    fused fold (the blob fixture above rarely leaves a whole chunk empty,
    so the lax.cond skip branch only gets traced there, not run): a
    corner blob leaves most chunks provably empty, occupancy must skip
    them, and the pallas fold must still match the xla fold and the
    skip_empty=False reference exactly."""
    size = 40
    z, y, x = np.meshgrid(*(np.linspace(-1, 1, size, dtype=np.float32),)
                          * 3, indexing="ij")
    field = np.exp(-(((x - 0.7) ** 2 + (y - 0.7) ** 2 + (z - 0.7) ** 2)
                     / 0.02)).astype(np.float32)
    vol = Volume.centered(jnp.asarray(field), extent=2.0)

    cam = Camera.create((0.3, 0.5, 2.8), fov_y_deg=45.0, near=0.3, far=10.0)
    spec_p = slicer.make_spec(cam, vol.data.shape, PALLAS)
    occ = np.asarray(slicer.chunk_occupancy(vol, tf, spec_p))
    assert (~occ).sum() >= 1, "fixture must leave at least one empty chunk"

    cfg = VDIConfig(max_supersegments=6, adaptive_mode="histogram",
                    histogram_bins=8)
    vdi_p, _, _ = slicer.generate_vdi_mxu(vol, tf, cam, spec_p, cfg)
    spec_x = slicer.make_spec(cam, vol.data.shape, XLA)
    vdi_x, _, _ = slicer.generate_vdi_mxu(vol, tf, cam, spec_x, cfg)
    spec_off = slicer.make_spec(
        cam, vol.data.shape,
        SliceMarchConfig(matmul_dtype="f32", scale=1.5, fold="pallas",
                         skip_empty=False))
    vdi_off, _, _ = slicer.generate_vdi_mxu(vol, tf, cam, spec_off, cfg)

    np.testing.assert_allclose(np.asarray(vdi_p.color),
                               np.asarray(vdi_x.color), rtol=2e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(vdi_p.color),
                               np.asarray(vdi_off.color), rtol=2e-6,
                               atol=1e-6)
    dp = np.nan_to_num(np.asarray(vdi_p.depth), posinf=1e9)
    dx = np.nan_to_num(np.asarray(vdi_x.depth), posinf=1e9)
    doff = np.nan_to_num(np.asarray(vdi_off.depth), posinf=1e9)
    np.testing.assert_allclose(dp, dx, rtol=2e-6, atol=1e-5)
    np.testing.assert_allclose(dp, doff, rtol=2e-6, atol=1e-5)


def test_fold_chunk_width_tiled_matches_sequential_push():
    """Multi-block width tiling (wb < w: 2D grid, masked partial last
    block) must match the sequential push exactly — the production
    trigger is frame widths whose strip VMEM estimate exceeds the
    budget (512^3 -> 640-wide strips OOM'd Mosaic's 16 MB scoped limit
    on hardware), which no test-sized frame reaches, so force the
    geometry through _FORCE_BLOCK_W: 320 = 128 + 128 + 64-masked."""
    h, w = 16, 320
    k, c = 6, 5
    rgba, t0, t1 = _stream(jax.random.PRNGKey(11), c, h, w)
    thr = jnp.full((h, w), 0.25, jnp.float32)

    st, cst = _fold_xla(rgba, t0, t1, thr, k)
    old = pm._FORCE_BLOCK_W
    pm._FORCE_BLOCK_W = 128
    try:
        packed, cnt = pm.fold_chunk(
            pm.init_packed(k, h, w), rgba, t0, t1, thr, max_k=k,
            count=jnp.zeros((h, w), jnp.int32), interpret=True)
        carry = pm.init_count_multi_packed(3, h, w)
        tvec = jnp.asarray([0.1, 0.25, 0.6])
        carry = pm.count_multi_chunk(carry, rgba, tvec, interpret=True)
    finally:
        pm._FORCE_BLOCK_W = old
    got = pm.unpack_state(packed)
    np.testing.assert_allclose(np.asarray(st.out_color),
                               np.asarray(got.out_color), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(
        np.nan_to_num(np.asarray(st.out_start), posinf=1e9),
        np.nan_to_num(np.asarray(got.out_start), posinf=1e9),
        rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(st.k), np.asarray(got.k))
    np.testing.assert_array_equal(np.asarray(cst.count), np.asarray(cnt))

    cm = ss.init_count_multi(3, h, w)
    for i in range(c):
        cm = ss.push_count(cm, tvec[:, None, None], rgba[i])
    np.testing.assert_array_equal(np.asarray(carry[0]),
                                  np.asarray(cm.count))


def test_fold_chunk_gated_phase2_matches_sequential_push():
    """_PHASE2_GATED skips the event extraction for slot rows with no
    close event anywhere in the block; the passthrough copy must leave
    those rows bit-identical and the gated rows must still extract
    exactly (same stream as the ungated parity test)."""
    h, w = 16, 40
    k, c = 6, 5
    rgba, t0, t1 = _stream(jax.random.PRNGKey(3), c, h, w)
    thr = jnp.full((h, w), 0.25, jnp.float32)
    st, _ = _fold_xla(rgba, t0, t1, thr, k)

    old = pm._PHASE2_GATED
    pm._PHASE2_GATED = True
    try:
        packed = pm.fold_chunk(pm.init_packed(k, h, w), rgba, t0, t1,
                               thr, max_k=k, interpret=True)
    finally:
        pm._PHASE2_GATED = old
    got = pm.unpack_state(packed)
    np.testing.assert_allclose(np.asarray(st.out_color),
                               np.asarray(got.out_color), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(
        np.nan_to_num(np.asarray(st.out_start), posinf=1e9),
        np.nan_to_num(np.asarray(got.out_start), posinf=1e9),
        rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(st.k), np.asarray(got.k))
