"""Parity tests for the segmented-scan write fold (ops/seg_fold.py): the
parallel formulation must produce the same supersegments as sequential
``ss.push`` calls — same break predicates, same merge-overflow, same
depths — differing only in fp association of the within-segment sums."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scenery_insitu_tpu.ops import seg_fold as sf
from scenery_insitu_tpu.ops import supersegments as ss


def _stream(key, n, h, w, empty_frac=0.4, dup_frac=0.3):
    """Depth-ordered stream with empty runs AND near-duplicate colors so
    all three paths fire: start-on-gap, break-on-diff, accumulate."""
    kr, ka, kd, ku = jax.random.split(key, 4)
    rgb = jax.random.uniform(kr, (n, 3, h, w))
    # near-duplicates: copy the previous item's color for ~dup_frac items
    # so diff <= thr accumulation paths are exercised
    dup = jax.random.uniform(ku, (n, 1, h, w)) < dup_frac
    rgb = jnp.where(dup & (jnp.arange(n)[:, None, None, None] > 0),
                    jnp.roll(rgb, 1, axis=0), rgb)
    alpha = jax.random.uniform(ka, (n, 1, h, w), minval=0.05, maxval=0.9)
    gate = jax.random.uniform(kd, (n, 1, h, w)) > empty_frac
    alpha = alpha * gate
    rgba = jnp.concatenate([rgb * alpha, alpha], axis=1)
    t0 = jnp.cumsum(jnp.full((n, h, w), 0.1), axis=0)
    return rgba, t0, t0 + 0.1


def _ref(rgba, t0, t1, thr, max_k):
    st = ss.init_state(max_k, rgba.shape[2], rgba.shape[3])
    cst = ss.init_count(rgba.shape[2], rgba.shape[3])
    for i in range(rgba.shape[0]):
        st = ss.push(st, max_k, thr, rgba[i], t0[i], t1[i])
        cst = ss.push_count(cst, thr, rgba[i])
    c, d = ss.finalize(st)
    return c, d, cst.count


def _seg(rgba, t0, t1, thr, max_k, chunks):
    st = sf.init_seg_state(max_k, rgba.shape[2], rgba.shape[3])
    lo = 0
    for c in chunks:
        st = sf.seg_fold_chunk(st, rgba[lo:lo + c], t0[lo:lo + c],
                               t1[lo:lo + c], thr, max_k=max_k)
        lo += c
    assert lo == rgba.shape[0]
    c_, d_ = sf.seg_finalize(st)
    return c_, d_, st.cnt


@pytest.mark.parametrize("chunks", [(12,), (7, 5), (1,) * 12, (3, 3, 3, 3)])
def test_matches_sequential_push(chunks):
    h, w = 16, 40
    max_k = 5
    rgba, t0, t1 = _stream(jax.random.PRNGKey(0), 12, h, w)
    thr = jnp.full((h, w), 0.35, jnp.float32)
    c_ref, d_ref, n_ref = _ref(rgba, t0, t1, thr, max_k)
    c_s, d_s, n_s = _seg(rgba, t0, t1, thr, max_k, chunks)
    np.testing.assert_array_equal(np.asarray(n_s), np.asarray(n_ref))
    np.testing.assert_allclose(np.asarray(c_s), np.asarray(c_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(d_s), np.asarray(d_ref),
                               rtol=1e-5, atol=1e-5)


def test_merge_overflow_parity():
    """Threshold 0 forces a break at every color change -> far more true
    segments than slots; the overflow tail must merge identically."""
    h, w = 8, 24
    max_k = 3
    rgba, t0, t1 = _stream(jax.random.PRNGKey(1), 20, h, w,
                           empty_frac=0.25, dup_frac=0.0)
    thr = jnp.zeros((h, w), jnp.float32)
    c_ref, d_ref, n_ref = _ref(rgba, t0, t1, thr, max_k)
    c_s, d_s, n_s = _seg(rgba, t0, t1, thr, max_k, (8, 12))
    np.testing.assert_array_equal(np.asarray(n_s), np.asarray(n_ref))
    np.testing.assert_allclose(np.asarray(c_s), np.asarray(c_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(d_s), np.asarray(d_ref),
                               rtol=1e-5, atol=1e-5)


def test_all_empty_and_leading_empty_chunks():
    h, w = 8, 16
    max_k = 4
    rgba, t0, t1 = _stream(jax.random.PRNGKey(2), 10, h, w)
    # force chunks 0-1 fully empty (the occupancy-skip path feeds exactly
    # this: explicit empty samples that must close open segments)
    rgba = rgba.at[:4].set(0.0)
    thr = jnp.full((h, w), 0.3, jnp.float32)
    c_ref, d_ref, n_ref = _ref(rgba, t0, t1, thr, max_k)
    c_s, d_s, n_s = _seg(rgba, t0, t1, thr, max_k, (2, 2, 6))
    np.testing.assert_array_equal(np.asarray(n_s), np.asarray(n_ref))
    np.testing.assert_allclose(np.asarray(c_s), np.asarray(c_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(d_s), np.asarray(d_ref),
                               rtol=1e-5, atol=1e-5)


def test_gap_splits_segment_across_chunk_boundary():
    """A segment open at a chunk boundary must continue (not restart):
    composition across the boundary uses the carried out_alpha."""
    h, w = 4, 8
    max_k = 4
    n = 6
    # constant color, constant alpha, no empties: ONE segment
    rgba = jnp.broadcast_to(
        jnp.asarray([0.2, 0.1, 0.05, 0.5], jnp.float32)[None, :, None, None],
        (n, 4, h, w))
    t0 = jnp.cumsum(jnp.full((n, h, w), 0.1), axis=0)
    thr = jnp.full((h, w), 0.5, jnp.float32)
    c_ref, d_ref, n_ref = _ref(rgba, t0, t0 + 0.1, thr, max_k)
    c_s, d_s, n_s = _seg(rgba, t0, t0 + 0.1, thr, max_k, (2, 2, 2))
    assert int(n_s.max()) == 1
    np.testing.assert_array_equal(np.asarray(n_s), np.asarray(n_ref))
    np.testing.assert_allclose(np.asarray(c_s), np.asarray(c_ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(d_s), np.asarray(d_ref),
                               rtol=1e-6, atol=1e-6)


def _ratios(rng, n, h, w):
    """Per-slice depth ratios, their step and a per-pixel ray length: the
    compact depth form; its planes are ``sk[:, None, None] * length``."""
    sk = jnp.asarray(np.sort(rng.random(n).astype(np.float32)) + 0.5)
    ds = jnp.float32(0.03)
    length = jnp.asarray(1.0 + rng.random((h, w), dtype=np.float32))
    return sk, sk + ds, length


def test_pallas_seg_matches_xla_seg():
    """The VMEM kernel's shaded feed (ops/pallas_seg.py, interpret mode
    off-TPU) must reproduce the XLA seg fold on the planes
    ``sk * length``, including carried state across chunks."""
    from scenery_insitu_tpu.ops import pallas_seg as psg

    h, w = 16, 40                          # w deliberately NOT 128-aligned
    max_k = 5
    rgba, _, _ = _stream(jax.random.PRNGKey(4), 12, h, w)
    sk0, sk1, length = _ratios(np.random.default_rng(4), 12, h, w)
    t0 = sk0[:, None, None] * length[None]
    t1 = sk1[:, None, None] * length[None]
    thr = jnp.full((h, w), 0.35, jnp.float32)
    st_x = sf.init_seg_state(max_k, h, w)
    packed = psg.init_seg_packed(max_k, h, w)
    for lo, n in ((0, 7), (7, 5)):
        st_x = sf.seg_fold_chunk(st_x, rgba[lo:lo + n], t0[lo:lo + n],
                                 t1[lo:lo + n], thr, max_k=max_k)
        packed = psg.fold_chunk_packed(
            packed, rgba[lo:lo + n], thr, max_k=max_k, sk0=sk0[lo:lo + n],
            sk1=sk1[lo:lo + n], length=length)
    st_p = psg.unpack_seg_state(packed)
    np.testing.assert_array_equal(np.asarray(st_p.cnt), np.asarray(st_x.cnt))
    for a, b, name in zip(sf.seg_finalize(st_x), sf.seg_finalize(st_p),
                          ("color", "depth")):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-6, atol=1e-6, err_msg=name)


@pytest.mark.parametrize("fold", ["pallas_seg", "pallas_fused"])
def test_whole_march_parity(fold):
    """generate_vdi_mxu + temporal: the kernel folds must reproduce the
    sequential-machine fold end to end, including the temporal threshold
    controller's feedback (integer counts must agree exactly)."""
    from scenery_insitu_tpu.config import SliceMarchConfig, VDIConfig
    from scenery_insitu_tpu.core.camera import Camera
    from scenery_insitu_tpu.core.transfer import for_dataset
    from scenery_insitu_tpu.core.volume import procedural_volume
    from scenery_insitu_tpu.ops import slicer

    vol = procedural_volume(40, kind="blobs", seed=7)
    tf = for_dataset("procedural")
    cam = Camera.create((0.25, 0.5, 2.6), fov_y_deg=45.0, near=0.3,
                        far=10.0)
    cfg = VDIConfig(max_supersegments=6, adaptive_mode="histogram",
                    histogram_bins=8)
    spec_x = slicer.make_spec(cam, vol.data.shape,
                              SliceMarchConfig(matmul_dtype="f32",
                                               scale=1.5, fold="xla"))
    spec_s = slicer.make_spec(cam, vol.data.shape,
                              SliceMarchConfig(matmul_dtype="f32",
                                               scale=1.5, fold=fold))
    vdi_x, _, _ = slicer.generate_vdi_mxu(vol, tf, cam, spec_x, cfg)
    vdi_s, _, _ = slicer.generate_vdi_mxu(vol, tf, cam, spec_s, cfg)
    np.testing.assert_allclose(np.asarray(vdi_s.color),
                               np.asarray(vdi_x.color),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(vdi_s.depth),
                               np.asarray(vdi_x.depth),
                               rtol=1e-5, atol=1e-5)

    cfg_t = VDIConfig(max_supersegments=6, adaptive_mode="temporal")
    thr_x = slicer.initial_threshold(vol, tf, cam, spec_x, cfg_t)
    thr_s = slicer.initial_threshold(vol, tf, cam, spec_s, cfg_t)
    for _ in range(2):
        vdi_x, _, _, thr_x = slicer.generate_vdi_mxu_temporal(
            vol, tf, cam, spec_x, thr_x, cfg_t)
        vdi_s, _, _, thr_s = slicer.generate_vdi_mxu_temporal(
            vol, tf, cam, spec_s, thr_s, cfg_t)
        np.testing.assert_allclose(np.asarray(vdi_s.color),
                                   np.asarray(vdi_x.color),
                                   rtol=1e-5, atol=1e-5)
        # thresholds bisect from identical integer counts -> exact
        np.testing.assert_allclose(np.asarray(thr_s.thr),
                                   np.asarray(thr_x.thr),
                                   rtol=1e-6, atol=1e-6)


def test_scalar_threshold_and_jit():
    h, w = 8, 16
    max_k = 4
    rgba, t0, t1 = _stream(jax.random.PRNGKey(3), 8, h, w)
    c_ref, d_ref, n_ref = _ref(rgba, t0, t1,
                               jnp.full((h, w), 0.4, jnp.float32), max_k)

    @jax.jit
    def run(rgba, t0, t1):
        st = sf.init_seg_state(max_k, h, w)
        st = sf.seg_fold_chunk(st, rgba, t0, t1, 0.4, max_k=max_k)
        c, d = sf.seg_finalize(st)
        return c, d, st.cnt

    c_s, d_s, n_s = run(rgba, t0, t1)
    np.testing.assert_array_equal(np.asarray(n_s), np.asarray(n_ref))
    np.testing.assert_allclose(np.asarray(c_s), np.asarray(c_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(d_s), np.asarray(d_ref),
                               rtol=1e-5, atol=1e-5)


def test_fused_with_vtiles_parity():
    """fold='pallas_fused' composed with in-plane occupancy tiles: gated
    row blocks emit the raw-mode -1 sentinel, which the fused kernel must
    treat exactly like the zero-alpha samples the ungated march feeds."""
    from scenery_insitu_tpu.config import SliceMarchConfig, VDIConfig
    from scenery_insitu_tpu.core.camera import Camera
    from scenery_insitu_tpu.core.transfer import for_dataset
    from scenery_insitu_tpu.core.volume import Volume
    from scenery_insitu_tpu.ops import slicer

    data = np.zeros((32, 32, 32), np.float32)
    data[4:12, 5:14, 6:16] = 0.8           # sparse corner blob
    vol = Volume.centered(jnp.asarray(data), extent=2.0)
    tf = for_dataset("procedural")
    cam = Camera.create((0.2, 0.3, 2.8), fov_y_deg=45.0, near=0.3,
                        far=10.0)
    cfg = VDIConfig(max_supersegments=5, adaptive=False, threshold=0.3)

    def gen(fold, vt):
        spec = slicer.make_spec(
            cam, vol.data.shape,
            SliceMarchConfig(matmul_dtype="f32", scale=1.0, fold=fold,
                             occupancy_vtiles=vt))
        vdi, _, _ = slicer.generate_vdi_mxu(vol, tf, cam, spec, cfg)
        return np.asarray(vdi.color), np.asarray(vdi.depth)

    c_ref, d_ref = gen("xla", 0)
    c_f, d_f = gen("pallas_fused", 4)
    np.testing.assert_allclose(c_f, c_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(d_f, d_ref, rtol=1e-5, atol=1e-5)


def test_k1_everything_merges():
    """max_k=1: the machine's merge-overflow degenerates to 'one slot
    absorbs the whole stream'; the seg formulation must reproduce it
    (single reset at the first non-empty item, no resets after)."""
    h, w = 8, 16
    rgba, t0, t1 = _stream(jax.random.PRNGKey(5), 14, h, w)
    thr = jnp.zeros((h, w), jnp.float32)   # break at every color change
    c_ref, d_ref, n_ref = _ref(rgba, t0, t1, thr, 1)
    c_s, d_s, n_s = _seg(rgba, t0, t1, thr, 1, (7, 7))
    np.testing.assert_array_equal(np.asarray(n_s), np.asarray(n_ref))
    np.testing.assert_allclose(np.asarray(c_s), np.asarray(c_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(d_s), np.asarray(d_ref),
                               rtol=1e-5, atol=1e-5)


def test_compact_depth_equals_td_planes():
    """fold_chunk_packed's compact depth form (sk ratios + length, depths
    computed in-kernel) must equal the XLA fold of the td planes when the
    planes are the same outer product the march would materialize
    (t = sk * length): the depth stream that never reaches HBM is a pure
    traffic change — whole packed state, 128-aligned strip."""
    from scenery_insitu_tpu.ops import pallas_seg as psg

    rng = np.random.default_rng(11)
    c, k, h, w = 6, 4, 8, 256
    rgba = jnp.asarray(rng.random((c, 4, h, w), dtype=np.float32))
    # sprinkle empties so segmentation paths (starts/gaps) are exercised
    rgba = rgba.at[:, 3].set(
        jnp.where(jnp.asarray(rng.random((c, h, w))) < 0.3, 0.0,
                  rgba[:, 3]))
    sk0, sk1, length = _ratios(rng, c, h, w)
    thr = jnp.full((h, w), 0.15, jnp.float32)

    ref = psg.pack_seg_state(sf.seg_fold_chunk(
        sf.init_seg_state(k, h, w), rgba, sk0[:, None, None] * length[None],
        sk1[:, None, None] * length[None], thr, max_k=k))
    got = psg.fold_chunk_packed(psg.init_seg_packed(k, h, w), rgba, thr,
                                max_k=k, sk0=sk0, sk1=sk1, length=length,
                                interpret=True)
    for a, b, name in zip(ref, got, ("color", "depth", "small")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6, err_msg=name)


# ---------------------------------------------------------------------------
# PR 46: what `slicer.fold=auto` takes on a TPU


def _tpu_auto_fold(monkeypatch, cam, shape):
    """The fold `auto` resolves to on a TPU: the backend's NAME is
    patched for `make_spec`'s resolution only and put back, so the march
    itself runs as on any CPU (interpret mode, f32 operands)."""
    from scenery_insitu_tpu.config import SliceMarchConfig
    from scenery_insitu_tpu.ops import slicer

    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        return slicer.make_spec(cam, shape, SliceMarchConfig()).fold


def _blob_field(shape, seed):
    """Smooth blobs in [0, 1] on a (D, H, W) grid, empty toward one
    corner so that occupancy gates have something to skip."""
    rng = np.random.default_rng(seed)
    z, y, x = np.meshgrid(*(np.linspace(-1, 1, n) for n in shape),
                          indexing="ij")
    out = np.zeros(shape, np.float32)
    for _ in range(5):
        c = rng.uniform(-0.1, 0.7, 3)
        out += rng.uniform(0.4, 0.9) * np.exp(
            -((z - c[0]) ** 2 + (y - c[1]) ** 2 + (x - c[2]) ** 2)
            / rng.uniform(0.02, 0.08))
    return np.clip(out, 0.0, 1.0)


# what the cells hold: the dataset cell's u8 operand at K = 20 on a depth
# that is no chunk multiple; in-plane occupancy gates; a planned band's
# ownership interval on the march axis; a march toward -axis
AUTO_TPU_CASES = {
    "u8_k20_depth27_chunk8": dict(
        shape=(27, 40, 48), dtype=np.uint8, k=20, chunk=8, eye_z=3.0),
    "vtiles4": dict(
        shape=(32, 32, 32), dtype=np.float32, k=6, chunk=8, eye_z=3.0,
        vtiles=4),
    "w_bounds": dict(
        shape=(32, 32, 32), dtype=np.float32, k=6, chunk=8, eye_z=3.0,
        w_bounds=(-0.55, 0.3)),
    "sign_minus_remainder": dict(
        shape=(27, 32, 32), dtype=np.float32, k=6, chunk=8, eye_z=3.0),
    "sign_plus_remainder_vtiles": dict(
        shape=(27, 32, 32), dtype=np.uint8, k=6, chunk=8, eye_z=-3.0,
        vtiles=4),
    # the 16-bit dataset cell (PR 49): two byte planes into the march,
    # a depth of 16 + 10, Beechnut's five-point tent (not monotone) as
    # the kernel's immediates, the blobs squeezed into the tent's band
    "u16_k20_depth26_tent": dict(
        shape=(26, 40, 48), dtype=np.uint16, k=20, chunk=16, eye_z=3.0,
        tf="beechnut"),
}


@pytest.mark.parametrize("case", sorted(AUTO_TPU_CASES))
def test_tpu_auto_path_matches_xla_fold(case, monkeypatch):
    """The schedule `auto` takes on a TPU (the march hands the fold its
    value plane, the kernel shades it) against the sequential XLA fold of
    the shaded chunk, through the generator the cells run
    (`generate_vdi_mxu_temporal`, two frames, so the controller's
    feedback is compared too): same supersegments, same counts."""
    from scenery_insitu_tpu.config import SliceMarchConfig, VDIConfig
    from scenery_insitu_tpu.core.camera import Camera
    from scenery_insitu_tpu.core.transfer import for_dataset
    from scenery_insitu_tpu.core.volume import Volume
    from scenery_insitu_tpu.ops import slicer

    p = AUTO_TPU_CASES[case]
    field = _blob_field(p["shape"], seed=len(case))
    if p["dtype"] == np.uint8:
        field = np.round(field * 255).astype(np.uint8)
    if p["dtype"] == np.uint16:
        field = np.round((0.40 + 0.12 * field) * 65535).astype(np.uint16)
    vol = Volume.centered(jnp.asarray(field), extent=2.0)
    assert vol.data.dtype == p["dtype"]
    tf = for_dataset(p.get("tf", "procedural"))
    cam = Camera.create((0.2, 0.4, p["eye_z"]), fov_y_deg=45.0, near=0.3,
                        far=10.0)
    fold = _tpu_auto_fold(monkeypatch, cam, vol.data.shape)
    cfg = VDIConfig(max_supersegments=p["k"], adaptive_mode="temporal")
    out = {}
    for name in ("xla", fold):
        spec = slicer.make_spec(
            cam, vol.data.shape,
            SliceMarchConfig(matmul_dtype="f32", scale=1.0, fold=name,
                             chunk=p["chunk"],
                             occupancy_vtiles=p.get("vtiles", 0)))
        assert spec.sign == (-1 if p["eye_z"] > 0 else 1)
        assert slicer.fold_schedule(spec, vol, tf) == name
        kw = dict(w_bounds=p.get("w_bounds"))
        thr = slicer.initial_threshold(vol, tf, cam, spec, cfg, **kw)
        frames = []
        for _ in range(2):
            vdi, _, _, thr = slicer.generate_vdi_mxu_temporal(
                vol, tf, cam, spec, thr, cfg, **kw)
            frames.append((np.asarray(vdi.color), np.asarray(vdi.depth),
                           np.asarray(thr.thr)))
        out[name] = frames
    assert fold != "xla"
    assert np.asarray(out["xla"][-1][0])[..., 3, :, :].max() > 0.05
    for (c_x, d_x, t_x), (c_f, d_f, t_f) in zip(out["xla"], out[fold]):
        np.testing.assert_allclose(c_f, c_x, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(d_f, d_x, rtol=1e-5, atol=1e-5)
        # thresholds bisect from identical integer counts -> exact
        np.testing.assert_allclose(t_f, t_x, rtol=1e-6, atol=1e-6)


def test_tpu_auto_selects_by_volume_rank_and_tf(monkeypatch):
    """On a TPU `auto` is the shade-in-kernel fold; per march the
    generators keep it for a scalar volume with a concrete transfer
    function and take `pallas_seg` (the same kernel, shaded feed) for a
    pre-shaded volume and for a transfer function that is traced. Off a
    TPU `auto` stays `xla`; an explicit schedule is never re-chosen."""
    from scenery_insitu_tpu.config import SliceMarchConfig
    from scenery_insitu_tpu.core.camera import Camera
    from scenery_insitu_tpu.core.transfer import for_dataset
    from scenery_insitu_tpu.core.volume import Volume
    from scenery_insitu_tpu.obs import Recorder, profiler
    from scenery_insitu_tpu.ops import slicer

    cam = Camera.create((0.2, 0.4, 3.0), fov_y_deg=45.0, near=0.3, far=10.0)
    shape = (16, 16, 16)
    assert slicer.make_spec(cam, shape, SliceMarchConfig()).fold == "xla"
    assert _tpu_auto_fold(monkeypatch, cam, shape) == "pallas_fused"
    tf = for_dataset("procedural")
    scalar = Volume.centered(jnp.asarray(_blob_field(shape, 1)), extent=2.0)
    shaded = Volume(jnp.zeros((4,) + shape, jnp.float32), scalar.origin,
                    scalar.spacing)
    auto = slicer.make_spec(cam, shape, SliceMarchConfig(
        matmul_dtype="f32", fold="pallas_fused"))
    assert slicer.fold_schedule(auto, scalar, tf) == "pallas_fused"
    assert slicer.fold_schedule(auto, shaded, None) == "pallas_seg"
    for name in ("xla", "pallas_seg"):
        spec = slicer.make_spec(cam, shape, SliceMarchConfig(
            matmul_dtype="f32", fold=name))
        assert slicer.fold_schedule(spec, scalar, tf) == name
        assert slicer.fold_schedule(spec, shaded, None) == name

    # what the generators run, by what they tell a recorded step: a
    # caller that jits over the TF gets the shaded feed, not an error
    from scenery_insitu_tpu.config import VDIConfig

    cfg = VDIConfig(max_supersegments=4, adaptive=False, threshold=0.3)

    def gen(vol, tf):
        return slicer.generate_vdi_mxu(vol, tf, cam, auto, cfg)[0].color

    def noted(fn, *args):
        rec = Recorder(enabled=True)
        color = profiler.scoped_step(fn, rec)(*args)
        return [rec.counters.get("fold_chunks", 0),
                rec.counters.get("fold_chunks_fused", 0)], np.asarray(color)

    n_closed, c_closed = noted(jax.jit(lambda v: gen(v, tf)), scalar)
    n_traced, c_traced = noted(jax.jit(gen), scalar, tf)
    n_shaded, _ = noted(jax.jit(lambda v: gen(v, None)), shaded)
    assert n_closed == [1, 1] and n_traced == [1, 0] and n_shaded == [1, 0]
    np.testing.assert_allclose(c_traced, c_closed, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# The chip's schedule (`pallas_fused`) against the reference (`xla`) through
# the generators, the count kernel, and what `slicer.fold` accepts (PR 47)

XLA = dict(matmul_dtype="f32", scale=1.5, fold="xla")
FUSED = dict(matmul_dtype="f32", scale=1.5, fold="pallas_fused")
TOL = dict(rtol=1e-5, atol=1e-5)       # test_whole_march_parity's


@pytest.fixture(scope="module")
def blobs():
    from scenery_insitu_tpu.core.volume import procedural_volume

    return procedural_volume(40, kind="blobs", seed=7)


@pytest.fixture(scope="module")
def tf():
    from scenery_insitu_tpu.core.transfer import for_dataset

    return for_dataset("procedural")


def _specs(cam, shape, **over):
    from scenery_insitu_tpu.config import SliceMarchConfig
    from scenery_insitu_tpu.ops import slicer

    return (slicer.make_spec(cam, shape, SliceMarchConfig(**{**XLA, **over})),
            slicer.make_spec(cam, shape,
                             SliceMarchConfig(**{**FUSED, **over})))


def _camera(eye):
    from scenery_insitu_tpu.core.camera import Camera

    return Camera.create(eye, fov_y_deg=45.0, near=0.3, far=10.0)


def test_count_multi_matches_push_count():
    from scenery_insitu_tpu.ops import pallas_seg as psg

    h, w = 16, 24
    bins = 6
    rgba, _, _ = _stream(jax.random.PRNGKey(5), 10, h, w)
    tvec = ss.threshold_candidates(bins, 2.0)

    st = ss.init_count_multi(bins, h, w)
    for i in range(rgba.shape[0]):
        st = ss.push_count(st, tvec[:, None, None], rgba[i])

    carry = psg.init_count_multi_packed(bins, h, w)
    carry = psg.count_multi_chunk(carry, rgba[:4], np.asarray(tvec))
    carry = psg.count_multi_chunk(carry, rgba[4:], np.asarray(tvec))
    np.testing.assert_array_equal(np.asarray(carry[0]),
                                  np.asarray(st.count))


def test_generate_vdi_mxu_fold_parity(blobs, tf):
    """Whole-march parity: fold='pallas_fused' must reproduce fold='xla'
    (histogram adaptive mode — the counting march through the count
    kernel, the write march through the fused kernel), on an x march:
    the one layout `permute_volume` transposes."""
    from scenery_insitu_tpu.config import VDIConfig
    from scenery_insitu_tpu.ops import slicer

    cam = _camera((2.6, 0.5, 0.25))
    cfg = VDIConfig(max_supersegments=6, adaptive_mode="histogram",
                    histogram_bins=8)
    spec_x, spec_p = _specs(cam, blobs.data.shape)
    assert spec_p.fold == "pallas_fused" and spec_x.fold == "xla"
    assert (spec_p.axis, spec_p.sign) == (0, -1)

    vdi_x, _, _ = slicer.generate_vdi_mxu(blobs, tf, cam, spec_x, cfg)
    vdi_p, _, _ = slicer.generate_vdi_mxu(blobs, tf, cam, spec_p, cfg)
    np.testing.assert_allclose(np.asarray(vdi_p.color),
                               np.asarray(vdi_x.color), **TOL)
    np.testing.assert_allclose(np.asarray(vdi_p.depth),
                               np.asarray(vdi_x.depth), **TOL)


def test_temporal_fold_parity(blobs, tf):
    """Temporal mode: the kernel's own running start count must produce
    the same VDI AND the same next-frame threshold state as the XLA
    side-by-side fold, across several carried frames."""
    from scenery_insitu_tpu.config import VDIConfig
    from scenery_insitu_tpu.ops import slicer

    cam = _camera((0.0, 0.4, 2.8))
    cfg = VDIConfig(max_supersegments=6, adaptive_mode="temporal")
    spec_x, spec_p = _specs(cam, blobs.data.shape)

    thr_x = slicer.initial_threshold(blobs, tf, cam, spec_x, cfg)
    thr_p = slicer.initial_threshold(blobs, tf, cam, spec_p, cfg)
    np.testing.assert_allclose(np.asarray(thr_p.thr),
                               np.asarray(thr_x.thr), rtol=2e-6, atol=1e-6)

    for _ in range(3):
        vdi_x, _, _, thr_x = slicer.generate_vdi_mxu_temporal(
            blobs, tf, cam, spec_x, thr_x, cfg)
        vdi_p, _, _, thr_p = slicer.generate_vdi_mxu_temporal(
            blobs, tf, cam, spec_p, thr_p, cfg)
        np.testing.assert_allclose(np.asarray(vdi_p.color),
                                   np.asarray(vdi_x.color), **TOL)
        np.testing.assert_allclose(np.asarray(vdi_p.depth),
                                   np.asarray(vdi_x.depth), **TOL)
        # thresholds bisect from identical integer counts -> exact
        np.testing.assert_allclose(np.asarray(thr_p.thr),
                                   np.asarray(thr_x.thr),
                                   rtol=1e-6, atol=1e-6)


def test_fold_parity_under_jit(blobs, tf):
    """The production call shape: the whole generate step jitted, the
    kernel fold inside — must still match and must be jit-stable."""
    from scenery_insitu_tpu.config import VDIConfig
    from scenery_insitu_tpu.ops import slicer

    cam = _camera((0.1, 0.5, 2.7))
    cfg = VDIConfig(max_supersegments=5, adaptive_mode="histogram",
                    histogram_bins=8)
    spec_x, spec_p = _specs(cam, blobs.data.shape)

    def gen(spec):
        @jax.jit
        def run(data):
            v = type(blobs)(data, blobs.origin, blobs.spacing)
            vdi, _, _ = slicer.generate_vdi_mxu(v, tf, cam, spec, cfg)
            return vdi.color, vdi.depth
        return run

    cp, dp = gen(spec_p)(blobs.data)
    cx, dx = gen(spec_x)(blobs.data)
    np.testing.assert_allclose(np.asarray(cp), np.asarray(cx), **TOL)
    np.testing.assert_allclose(np.asarray(dp), np.asarray(dx), **TOL)


def test_auto_fold_resolution(monkeypatch):
    """"auto" resolves by backend NAME — the XLA fold off-TPU
    (interpret-mode pallas is slow; conftest pins the cpu backend), the
    seg kernel that shades the march's value plane itself on TPU (since
    PR 46; `slicer.fold_schedule` gives a march without a scalar volume
    or a concrete TF its shaded feed) with no compile probe in between
    (a Mosaic refusal raises at compile time) — and an explicit fold
    choice is always honored."""
    from scenery_insitu_tpu.config import SliceMarchConfig
    from scenery_insitu_tpu.ops import slicer

    assert jax.default_backend() == "cpu"        # conftest invariant
    cam = _camera((0.0, 0.4, 2.8))
    spec = slicer.make_spec(cam, (16, 16, 16), SliceMarchConfig())
    assert spec.fold == "xla"
    spec_p = slicer.make_spec(cam, (16, 16, 16),
                              SliceMarchConfig(fold="pallas_seg"))
    assert spec_p.fold == "pallas_seg"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spec_t = slicer.make_spec(cam, (16, 16, 16), SliceMarchConfig())
    assert spec_t.fold == "pallas_fused"


@pytest.mark.parametrize("name", ["pallas", "seg", "fused_stream"])
def test_make_spec_rejects_a_removed_schedule(name):
    """A configuration file or `--set` from outside that still names a
    schedule PR 47 removed is refused where the spec is made, by name."""
    from scenery_insitu_tpu.config import SliceMarchConfig
    from scenery_insitu_tpu.ops import slicer

    with pytest.raises(ValueError, match=f"unknown fold schedule '{name}'"):
        slicer.make_spec(_camera((0.0, 0.4, 2.8)), (16, 16, 16),
                         SliceMarchConfig(fold=name))


def test_skip_chunks_execute_through_pallas_fold(tf):
    """Occupancy skipping EXECUTES the C=1 dead-sample branch through the
    fused fold (the blob fixture above rarely leaves a whole chunk empty,
    so the lax.cond skip branch only gets traced there, not run): a
    corner blob leaves most chunks provably empty, occupancy must skip
    them, and the kernel fold must still match the xla fold and the
    skip_empty=False reference."""
    from scenery_insitu_tpu.config import VDIConfig
    from scenery_insitu_tpu.core.volume import Volume
    from scenery_insitu_tpu.ops import slicer

    size = 40
    z, y, x = np.meshgrid(*(np.linspace(-1, 1, size, dtype=np.float32),)
                          * 3, indexing="ij")
    field = np.exp(-(((x - 0.7) ** 2 + (y - 0.7) ** 2 + (z - 0.7) ** 2)
                     / 0.02)).astype(np.float32)
    vol = Volume.centered(jnp.asarray(field), extent=2.0)

    cam = _camera((0.3, 0.5, 2.8))
    spec_x, spec_p = _specs(cam, vol.data.shape)
    occ = np.asarray(slicer.chunk_occupancy(vol, tf, spec_p))
    assert (~occ).sum() >= 1, "fixture must leave at least one empty chunk"

    cfg = VDIConfig(max_supersegments=6, adaptive_mode="histogram",
                    histogram_bins=8)
    vdi_p, _, _ = slicer.generate_vdi_mxu(vol, tf, cam, spec_p, cfg)
    vdi_x, _, _ = slicer.generate_vdi_mxu(vol, tf, cam, spec_x, cfg)
    _, spec_off = _specs(cam, vol.data.shape, skip_empty=False)
    vdi_off, _, _ = slicer.generate_vdi_mxu(vol, tf, cam, spec_off, cfg)

    np.testing.assert_allclose(np.asarray(vdi_p.color),
                               np.asarray(vdi_x.color), **TOL)
    np.testing.assert_allclose(np.asarray(vdi_p.color),
                               np.asarray(vdi_off.color), **TOL)
    dp = np.nan_to_num(np.asarray(vdi_p.depth), posinf=1e9)
    dx = np.nan_to_num(np.asarray(vdi_x.depth), posinf=1e9)
    doff = np.nan_to_num(np.asarray(vdi_off.depth), posinf=1e9)
    np.testing.assert_allclose(dp, dx, **TOL)
    np.testing.assert_allclose(dp, doff, **TOL)


@pytest.mark.parametrize("kernel", ["fused", "compact", "count"])
def test_width_tiled_strips_match_the_xla_reference(kernel, tf, monkeypatch):
    """Multi-block width tiling (wb < w: 2D grid, masked partial last
    block) of each kernel against its XLA reference. The cells' 640- and
    1280-wide frames tile the fused kernel at 256 / 384; no test-sized
    frame exceeds the strip budget, so the geometry is forced:
    320 = 128 + 128 + 64 masked."""
    from scenery_insitu_tpu.ops import pallas_seg as psg
    from scenery_insitu_tpu.ops import pallas_util
    from scenery_insitu_tpu.ops.sampling import adjust_opacity

    h, w = 16, 320
    k, c = 6, 5
    monkeypatch.setattr(pallas_util, "_FORCE_BLOCK_W", 128)
    assert pallas_util.pick_block_w(w, 1) == 128
    rgba, _, _ = _stream(jax.random.PRNGKey(11), c, h, w)

    if kernel == "count":
        tvec = jnp.asarray([0.1, 0.25, 0.6])
        carry = psg.count_multi_chunk(psg.init_count_multi_packed(3, h, w),
                                      rgba, tvec, interpret=True)
        cm = ss.init_count_multi(3, h, w)
        for i in range(c):
            cm = ss.push_count(cm, tvec[:, None, None], rgba[i])
        np.testing.assert_array_equal(np.asarray(carry[0]),
                                      np.asarray(cm.count))
        return

    rng = np.random.default_rng(11)
    sk0, sk1, length = _ratios(rng, c, h, w)
    thr = jnp.full((h, w), 0.25, jnp.float32)
    packed = psg.init_seg_packed(k, h, w)
    if kernel == "fused":
        # a value plane with dead samples; the reference shades it as
        # slice_march does (TF, dead -> transparent, opacity correction)
        val = jnp.asarray(rng.random((c, h, w), dtype=np.float32))
        val = jnp.where(jnp.asarray(rng.random((c, h, w))) < 0.3, -1.0, val)
        ratio = jnp.asarray(0.5 + rng.random((h, w), dtype=np.float32))
        rgb, alpha = tf(val)
        alpha = adjust_opacity(jnp.where(val < -0.5, 0.0, alpha),
                               ratio[None])
        rgba = jnp.concatenate([jnp.moveaxis(rgb, -1, 1) * alpha[:, None],
                                alpha[:, None]], axis=1)
        got = psg.fused_fold_chunk(packed, val, length, ratio, sk0, sk1,
                                   thr, max_k=k, tf=tf, interpret=True)
    else:
        got = psg.fold_chunk_packed(packed, rgba, thr, max_k=k, sk0=sk0,
                                    sk1=sk1, length=length, interpret=True)
    ref = psg.pack_seg_state(sf.seg_fold_chunk(
        sf.init_seg_state(k, h, w), rgba, sk0[:, None, None] * length[None],
        sk1[:, None, None] * length[None], thr, max_k=k))
    assert float(np.asarray(ref[2][0]).max()) >= 2     # segments were cut
    for a, b, name in zip(ref, got, ("color", "depth", "small")):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   err_msg=name, **TOL)


@pytest.mark.parametrize("fold", ["xla", "pallas_seg", "pallas_fused"])
def test_one_dispatch_serves_both_generators(fold, tf):
    """`slicer.write_march` is the one write march: the plain generator
    at a fixed threshold and the temporal generator seeded with the same
    map write the SAME VDI, and the count the temporal generator's
    controller read is `ss.push_count`'s at that threshold."""
    from scenery_insitu_tpu.config import SliceMarchConfig, VDIConfig
    from scenery_insitu_tpu.core.volume import Volume
    from scenery_insitu_tpu.ops import slicer

    vol = Volume.centered(jnp.asarray(_blob_field((24, 24, 24), 3)),
                          extent=2.0)
    cam = _camera((0.2, 0.4, 3.0))
    spec = slicer.make_spec(cam, vol.data.shape, SliceMarchConfig(
        matmul_dtype="f32", scale=1.0, fold=fold, chunk=8))
    assert slicer.fold_schedule(spec, vol, tf) == fold
    fixed = VDIConfig(max_supersegments=4, adaptive=False, threshold=0.12)
    temporal = VDIConfig(max_supersegments=4, adaptive_mode="temporal")
    thr0 = ss.init_threshold_state(
        jnp.full((spec.nj, spec.ni), fixed.threshold, jnp.float32),
        temporal.thr_min, temporal.thr_max)

    vdi_f, _, axcam = slicer.generate_vdi_mxu(vol, tf, cam, spec, fixed)
    vdi_t, _, _, thr1 = slicer.generate_vdi_mxu_temporal(
        vol, tf, cam, spec, thr0, temporal)
    np.testing.assert_array_equal(np.asarray(vdi_t.color),
                                  np.asarray(vdi_f.color))
    np.testing.assert_array_equal(np.asarray(vdi_t.depth),
                                  np.asarray(vdi_f.depth))

    def consume(st, rgba, t0, t1):
        for i in range(rgba.shape[0]):
            st = ss.push_count(st, thr0.thr, rgba[i])
        return st

    count = slicer.slice_march(vol, tf, axcam, spec, consume,
                               ss.init_count(spec.nj, spec.ni)).count
    assert int(np.asarray(count).max()) > temporal.max_supersegments
    want = ss.update_threshold(thr0, count, temporal.max_supersegments,
                               temporal.adaptive_delta, temporal.thr_min,
                               temporal.thr_max, temporal.temporal_track)
    for a, b, name in zip(want, thr1, ("thr", "lo", "hi")):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a),
                                      err_msg=name)


# ---------------------------------------------------------------------------
# PR 50: phase B's trip count follows the data (the hull of each 8 x 128
# tile's slot intervals is merged, the rows outside it are copied)


def _dense_phase_b(ev_ref, len_ref, sk0_ref, sk1_ref, ci_, di_, co, do_,
                   si_, so, first, last, max_k, width):
    """The reference: PR 49's phase B, every one of the K slot rows of
    the whole strip merged whatever the records hold (it counts
    nothing)."""
    from jax.experimental import pallas as pl

    ev = ev_ref[...]
    ln = len_ref[...]
    t0a = sk0_ref[...] * ln[None]
    t1a = sk1_ref[...] * ln[None]
    ev_slot, ev_rgba = ev[:, 0], ev[:, 1:5]

    def slot_body(kk, _):
        m = ev_slot == kk.astype(jnp.float32)
        mf = m.astype(jnp.float32)
        contrib = jnp.sum(ev_rgba * mf[:, None], axis=0)
        d0 = jnp.min(jnp.where(m, t0a, jnp.inf), axis=0)
        d1 = jnp.max(jnp.where(m, t1a, -jnp.inf), axis=0)
        oc = ci_[pl.dslice(kk, 1)]
        co[pl.dslice(kk, 1)] = oc + (1.0 - oc[:, 3:4]) * contrib[None]
        dr = di_[pl.dslice(kk, 1)]
        do_[pl.dslice(kk, 1)] = jnp.stack(
            [jnp.minimum(dr[0, 0], d0), jnp.maximum(dr[0, 1], d1)])[None]
        return 0

    jax.lax.fori_loop(0, max_k, slot_body, 0)
    so[...] = si_[...]


def _shade(tf, val, ratio):
    """A value chunk (-1 = dead) shaded as `slice_march` shades it."""
    from scenery_insitu_tpu.ops.sampling import adjust_opacity

    rgb, alpha = tf(val)
    alpha = adjust_opacity(jnp.where(val < -0.5, 0.0, alpha), ratio[None])
    return jnp.concatenate([jnp.moveaxis(rgb, -1, 1) * alpha[:, None],
                            alpha[:, None]], axis=1)


# name -> (K, chunk sizes); every stream is 16 x 300 pixels (tiles of
# 128, 128 and 44 lanes), threshold 0: a sample starts a segment iff its
# colour differs at all from the one before, or follows a gap, so the
# in-kernel and the XLA shading cut the same segments
SLOT_STREAMS = {
    "dead_tile": (6, (5, 5, 5)),
    "leading_empty": (6, (5, 5, 5)),
    "disjoint": (8, (5, 2)),
    "overflow": (4, (5, 5, 5)),
    "k20": (20, (5, 5, 5)),
    "remainder": (6, (5, 5, 2)),
}


def _slot_stream(case):
    """The chunks of ``case`` as value planes f32[C, 16, 300] with -1 for
    a dead sample: ~30 % dead, ~30 % repeats of the sample before (they
    accumulate into its segment), and what the case is named for."""
    k, sizes = SLOT_STREAMS[case]
    h, w, n = 16, 300, sum(sizes)
    rng = np.random.default_rng(sorted(SLOT_STREAMS).index(case))
    val = (0.2 + 0.8 * rng.random((n, h, w))).astype(np.float32)
    for s in range(1, n):
        val[s] = np.where(rng.random((h, w)) < 0.3, val[s - 1], val[s])
    val = np.where(rng.random((n, h, w)) < 0.3, -1.0, val)
    if case == "dead_tile":
        val[:, :, 128:256] = -1.0           # no live sample, any chunk
        val[5:10, :8, :128] = -1.0          # and one dead for a chunk
    elif case == "leading_empty":
        val[:5] = -1.0
    elif case == "disjoint":
        # the left half of every tile sleeps through chunk 0 while the
        # right half opens five segments; in chunk 1 the halves land in
        # slots 0-1 and 5-6: the hull holds rows nobody merges into
        val[:] = (0.2 + 0.8 * rng.random((n, h, w))).astype(np.float32)
        lanes = (np.arange(w) % 128) < 64
        val[:5, :, lanes] = -1.0
    return [jnp.asarray(val[lo:lo + c].astype(np.float32))
            for lo, c in zip(np.cumsum((0,) + sizes[:-1]), sizes)], k


def _fold_stream(feed, tf, chunks, k, geo, interpret=True):
    """The stream through one of the kernel's feeds -> the packed
    quadruple as numpy."""
    from scenery_insitu_tpu.ops import pallas_seg as psg

    sk0, sk1, length, ratio, thr = geo
    packed = psg.init_seg_packed(k, *length.shape)
    lo = 0
    for val in chunks:
        a, b = sk0[lo:lo + val.shape[0]], sk1[lo:lo + val.shape[0]]
        if feed == "fused":
            packed = psg.fused_fold_chunk(packed, val, length, ratio, a, b,
                                          thr, max_k=k, tf=tf,
                                          interpret=interpret)
        else:
            packed = psg.fold_chunk_packed(packed, _shade(tf, val, ratio),
                                           thr, max_k=k, sk0=a, sk1=b,
                                           length=length,
                                           interpret=interpret)
        lo += val.shape[0]
    return [np.asarray(x) for x in packed]


def _geometry(n, h, w, seed=50):
    rng = np.random.default_rng(seed)
    sk0, sk1, length = _ratios(rng, n, h, w)
    ratio = jnp.asarray(0.5 + rng.random((h, w), dtype=np.float32))
    return sk0, sk1, length, ratio, jnp.zeros((h, w), jnp.float32)


def _numpy_slot_rows(tf, chunks, k, geo):
    """(rows merged, rows visited) as the kernel defines them, from
    `seg_fold.chunk_flags` on the same stream: per chunk and 8 x 128
    tile, the hull of the slots its live samples land in."""
    sk0, sk1, length, ratio, thr = geo
    h, w = length.shape
    st = sf.init_seg_state(k, h, w)
    merged = rows = lo = 0
    for val in chunks:
        c = val.shape[0]
        rgba = _shade(tf, val, ratio)
        emp, starts = sf.chunk_flags(rgba, st.prev_rgb, st.prev_empty, thr)
        sid = np.asarray(st.cnt)[None] + np.cumsum(
            np.asarray(starts), axis=0) - 1
        slot = np.where(np.asarray(emp), -1, np.minimum(sid, k - 1))
        for j in range(0, h, 8):
            for i in range(0, w, 128):
                tile = slot[:, j:j + 8, i:i + 128]
                rows += k
                if (tile >= 0).any():
                    merged += tile.max() + 1 - tile[tile >= 0].min()
        st = sf.seg_fold_chunk(
            st, rgba, sk0[lo:lo + c, None, None] * length[None],
            sk1[lo:lo + c, None, None] * length[None], thr, max_k=k)
        lo += c
    return int(merged), int(rows)


@pytest.mark.parametrize("feed", ["fused", "compact"])
@pytest.mark.parametrize("case", sorted(SLOT_STREAMS))
def test_bounded_slot_loop_is_the_dense_loop_bit_for_bit(case, feed, tf,
                                                         monkeypatch):
    """Merging only a tile's hull of slot rows and copying the others
    gives the packed state the dense loop gives, to the bit, under both
    feeds; and the kernel's count of the rows it merged is the one NumPy
    makes from `seg_fold.chunk_flags` on the same stream."""
    from scenery_insitu_tpu.ops import pallas_seg as psg

    chunks, k = _slot_stream(case)
    geo = _geometry(sum(c.shape[0] for c in chunks), 16, 300)
    got = _fold_stream(feed, tf, chunks, k, geo)
    with monkeypatch.context() as m:
        m.setattr(psg, "_phase_b_compact", _dense_phase_b)
        ref = _fold_stream(feed, tf, chunks, k, geo)
    cnt = got[2][0]
    assert cnt.max() >= (k if case in ("overflow",) else 2)
    for a, b, name in zip(got, ref, ("color", "depth", "small")):
        assert np.array_equal(a, b), name
    assert not ref[3].any()
    merged, rows = _numpy_slot_rows(tf, chunks, k, geo)
    assert tuple(got[3].sum(axis=1)) == (merged, rows)
    assert 0 < merged < rows
    if case == "dead_tile":     # tiles 0-2 of the two row bands
        assert not got[3][0, [1, 4]].any() and got[3][0, [0, 3]].all()


def test_slot_hull_holds_rows_nobody_merges_into(tf):
    """The `disjoint` stream's second chunk: every pixel lands in two
    slots, the tile's hull is seven rows wide (0-1 and 5-6 with 2-4
    between), and the rows between come out as they went in."""
    chunks, k = _slot_stream("disjoint")
    geo = _geometry(7, 16, 300)
    before = _fold_stream("fused", tf, chunks[:1], k, geo)
    after = _fold_stream("fused", tf, chunks, k, geo)
    # (the 44-lane tile holds left halves only: slots 0-1)
    np.testing.assert_array_equal(after[3][0] - before[3][0],
                                  [7, 7, 2, 7, 7, 2])
    lanes = (np.arange(300) % 128) < 64
    # rows 2-4: empty on the left half, untouched on the right
    assert not after[0][2:5][..., lanes].any()
    assert np.array_equal(after[0][2:5][..., ~lanes],
                          before[0][2:5][..., ~lanes])
    assert after[0][:2][..., lanes].any() and after[0][5:7].any()


@pytest.mark.parametrize("feed", ["fused", "compact"])
def test_bounded_slot_loop_on_width_tiled_strips(feed, tf, monkeypatch):
    """The width-tiled grid (300 = 256 + 44: the last block is narrower
    than the block AND than 128 lanes, its second 128-lane group lies
    wholly outside the image and its padding holds whatever interpret
    mode pads with): the same bits as the dense loop on the full row,
    and the same count."""
    from scenery_insitu_tpu.ops import pallas_seg as psg
    from scenery_insitu_tpu.ops import pallas_util

    chunks, k = _slot_stream("dead_tile")
    geo = _geometry(15, 16, 300)
    with monkeypatch.context() as m:
        m.setattr(psg, "_phase_b_compact", _dense_phase_b)
        ref = _fold_stream(feed, tf, chunks, k, geo)
    full = _fold_stream(feed, tf, chunks, k, geo)
    monkeypatch.setattr(pallas_util, "_FORCE_BLOCK_W", 256)
    assert pallas_util.pick_block_w(300, 1) == 256
    got = _fold_stream(feed, tf, chunks, k, geo)
    for a, b, name in zip(got, ref, ("color", "depth", "small")):
        assert np.array_equal(a, b), name
    assert np.array_equal(got[3], full[3]) and got[3][0].any()


@pytest.mark.parametrize("junk", [np.nan, np.inf, -np.inf, -7.0, 1e30])
def test_slot_bounds_ignore_what_the_padding_holds(junk):
    """`tile_slot_bounds` alone: whatever the lanes at or beyond the
    image's width hold, the bounds are those of the lanes inside, in
    [0, K] with lo <= hi; and junk INSIDE the image cannot take them out
    of that range either."""
    from scenery_insitu_tpu.ops import pallas_seg as psg

    k, width, col0 = 20, 300, 256           # 44 lanes of the tile inside
    rng = np.random.default_rng(3)
    first = rng.integers(3, 9, (8, 128)).astype(np.float32)
    last = first + rng.integers(0, 5, (8, 128)).astype(np.float32)
    first[2:4], last[2:4] = k, -1.0         # pixels with no live sample
    inside = np.arange(128) < width - col0
    want = (int(first[:, inside].min()), int(last[:, inside].max()) + 1)
    first[:, ~inside], last[:, ~inside] = junk, junk
    lo, hi = psg.tile_slot_bounds(jnp.asarray(first), jnp.asarray(last),
                                  col0, width, k)
    assert (int(lo), int(hi)) == want
    # no live sample inside: nothing to merge, whatever is outside
    first[:, inside], last[:, inside] = k, -1.0
    lo, hi = psg.tile_slot_bounds(jnp.asarray(first), jnp.asarray(last),
                                  col0, width, k)
    assert (int(lo), int(hi)) == (0, 0)
    # junk everywhere (it cannot happen inside the image: a guard)
    lo, hi = psg.tile_slot_bounds(jnp.full((8, 128), junk, jnp.float32),
                                  jnp.full((8, 128), junk, jnp.float32),
                                  0, width, k)
    assert 0 <= int(lo) <= int(hi) <= k


def test_slot_rows_of_a_dense_chunk_and_of_a_dead_march(tf):
    """`fold_slot_rows_merged` is `fold_slot_rows` where every tile's
    samples span all K slots, and 0 for a march with no live sample (a
    field the transfer function leaves transparent)."""
    from scenery_insitu_tpu.config import SliceMarchConfig, VDIConfig
    from scenery_insitu_tpu.core.volume import Volume
    from scenery_insitu_tpu.obs.profiler import fold_slot_account
    from scenery_insitu_tpu.ops import pallas_seg as psg
    from scenery_insitu_tpu.ops import slicer

    rng = np.random.default_rng(5)
    val = jnp.asarray((0.2 + 0.8 * rng.random((5, 16, 300)))
                      .astype(np.float32))
    dense = _fold_stream("fused", tf, [val], 4, _geometry(5, 16, 300))
    assert dense[2][0].min() == 5                  # five starts a pixel
    assert np.array_equal(dense[3][0], dense[3][1])
    assert dense[3][1].sum() == 4 * 2 * 3

    vol = Volume.centered(jnp.zeros((24, 24, 24), jnp.float32), extent=2.0)
    cam = _camera((0.2, 0.4, 3.0))
    spec = slicer.make_spec(cam, vol.data.shape, SliceMarchConfig(
        matmul_dtype="f32", scale=1.0, fold="pallas_fused", chunk=8,
        skip_empty=False))
    cfg = VDIConfig(max_supersegments=4, adaptive=False, threshold=0.1)
    with fold_slot_account(True) as noted:
        vdi, _, _ = slicer.generate_vdi_mxu(vol, tf, cam, spec, cfg)
    (slots,) = noted
    tiles = psg.slot_tiles(spec.nj, spec.ni)
    assert tuple(np.asarray(slots)) == (0, 4 * tiles * 3)
    assert not np.asarray(vdi.color).any()
    # nobody listening: the march says it to nobody
    with fold_slot_account(False) as noted:
        slicer.generate_vdi_mxu(vol, tf, cam, spec, cfg)
    assert noted is None


def test_four_rank_step_hands_each_ranks_slot_rows_on(tf):
    """A four-rank march under `shard_map` (`distributed_vdi_step_mxu`
    built with ``slot_counts``): the frame comes with i32[4, 2], each
    rank's own (merged, visited) of its slab's march; built without, the
    frame is the pair it always was, bit for bit."""
    from scenery_insitu_tpu.config import (CompositeConfig, SliceMarchConfig,
                                           VDIConfig)
    from scenery_insitu_tpu.ops import pallas_seg as psg
    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.parallel import pipeline
    from scenery_insitu_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(4)
    field = jnp.asarray(_blob_field((32, 32, 32), 4))
    cam = _camera((0.2, 0.4, 3.0))
    spec = slicer.make_spec(cam, field.shape, SliceMarchConfig(
        matmul_dtype="f32", scale=1.0, fold="pallas_fused", chunk=8),
        multiple_of=4)
    cfg = VDIConfig(max_supersegments=4, adaptive=False, threshold=0.1)
    comp = CompositeConfig(max_output_supersegments=4)
    args = (pipeline.shard_volume(field, mesh),
            jnp.full((3,), -1.0, jnp.float32),
            jnp.full((3,), 2.0 / 32, jnp.float32), cam)
    vdi, _, slots = pipeline.distributed_vdi_step_mxu(
        mesh, tf, spec, cfg, comp, slot_counts=True)(*args)
    slots = np.asarray(slots)
    tiles = psg.slot_tiles(spec.nj, spec.ni)
    assert slots.shape == (4, 2)
    # a rank's slab is 8 planes deep: one chunk a rank
    assert (slots[:, 1] == 4 * tiles).all()
    assert (slots[:, 0] <= slots[:, 1]).all() and slots[:, 0].sum() > 0
    assert len(set(slots[:, 0])) > 1       # each rank's own, not a copy
    plain, _ = pipeline.distributed_vdi_step_mxu(
        mesh, tf, spec, cfg, comp)(*args)
    assert np.array_equal(np.asarray(vdi.color), np.asarray(plain.color))
    assert np.array_equal(np.asarray(vdi.depth), np.asarray(plain.depth))


@pytest.mark.parametrize("ranks", [1, 4])
def test_session_counts_the_slot_rows_where_it_fetches(ranks):
    """A recorded session adds every fetched frame's account to
    `fold_slot_rows_merged` / `fold_slot_rows` (on a mesh each rank's
    own, summed); a session that records nothing has neither counter
    and its steps hand nothing on."""
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.session import InSituSession

    def run(recorded):
        sess = InSituSession(FrameworkConfig().with_overrides(
            "sim.grid=[32,32,32]", "sim.steps_per_frame=2",
            "slicer.engine=mxu", "slicer.fold=pallas_fused",
            "slicer.chunk=8", "vdi.adaptive_mode=temporal",
            "vdi.max_supersegments=6",
            "composite.max_output_supersegments=6",
            "runtime.dataset=gray_scott", f"mesh.num_devices={ranks}",
            f"obs.enabled={str(recorded).lower()}"))
        frames = []
        sess.sinks.append(lambda i, p: frames.append(p["vdi_color"].copy()))
        sess.run(3)
        sess.close()
        assert all(v[2] is None for v in sess._pending_meta.values())
        return dict(sess.obs.counters), frames

    counters, frames = run(True)
    # 40 x 40 pixels: five row bands of one tile; 32 planes in chunks
    # of 8, over the ranks or in a row: four kernel calls a frame
    assert counters["fold_slot_rows"] == 3 * 4 * 6 * 5
    assert 0 < counters["fold_slot_rows_merged"] < counters["fold_slot_rows"]
    off, frames_off = run(False)
    assert "fold_slot_rows" not in off and "fold_slot_rows_merged" not in off
    for a, b in zip(frames, frames_off):
        assert np.array_equal(a, b)
