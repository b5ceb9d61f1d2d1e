"""Compositing tests: the dump->recomposite->compare loop the reference runs
by eye (VDICompositingExample) becomes numeric golden checks here."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scenery_insitu_tpu.config import CompositeConfig, RenderConfig, VDIConfig
from scenery_insitu_tpu.core.camera import Camera
from scenery_insitu_tpu.core.transfer import TransferFunction
from scenery_insitu_tpu.core.vdi import VDI, render_vdi_same_view
from scenery_insitu_tpu.core.volume import Volume, procedural_volume
from scenery_insitu_tpu.ops.composite import (composite_depth_min,
                                              composite_plain, composite_vdis,
                                              sort_stream)
from scenery_insitu_tpu.ops.raycast import raycast
from scenery_insitu_tpu.ops.vdi_gen import generate_vdi
from scenery_insitu_tpu.utils.image import psnr

W = H = 16
STEPS = 48


def _cam():
    return Camera.create((0.0, 0.0, 4.0), fov_y_deg=50.0, near=0.5, far=20.0)


def _split_z(vol: Volume, parts: int):
    """Domain-decompose along the volume z axis (≅ OpenFPM grid splits)."""
    d = vol.data.shape[0]
    chunk = d // parts
    subs = []
    for p in range(parts):
        data = vol.data[p * chunk:(p + 1) * chunk]
        origin = vol.origin + jnp.array([0.0, 0.0, p * chunk]) * vol.spacing
        subs.append(Volume(data, origin, vol.spacing))
    return subs


def test_two_rank_composite_matches_full_render():
    vol = procedural_volume(16, kind="shell")
    tf = TransferFunction.ramp(0.05, 0.8, 0.7)
    cam = _cam()
    ref = np.asarray(raycast(vol, tf, cam, W, H,
                             RenderConfig(max_steps=STEPS,
                                          early_exit_alpha=1.1)).image)
    vcfg = VDIConfig(max_supersegments=12)
    subs = _split_z(vol, 2)
    vdis = [generate_vdi(s, tf, cam, W, H, vcfg, max_steps=STEPS)[0]
            for s in subs]
    colors = jnp.stack([v.color for v in vdis])
    depths = jnp.stack([v.depth for v in vdis])
    out = composite_vdis(colors, depths,
                         CompositeConfig(max_output_supersegments=16))
    img = np.asarray(render_vdi_same_view(out))
    assert psnr(ref, img) > 28.0, psnr(ref, img)


def test_composite_preserves_order_of_disjoint_segments():
    # rank 0 has a far segment, rank 1 a near one; composite must put the
    # near one in front regardless of rank order
    k = 4
    v0 = VDI.empty(k, 1, 1)
    v0 = VDI(v0.color.at[0].set(jnp.array([0.0, 0.8, 0.0, 0.8]).reshape(4, 1, 1)),
             v0.depth.at[0].set(jnp.array([5.0, 5.5]).reshape(2, 1, 1)))
    v1 = VDI.empty(k, 1, 1)
    v1 = VDI(v1.color.at[0].set(jnp.array([0.9, 0.0, 0.0, 0.9]).reshape(4, 1, 1)),
             v1.depth.at[0].set(jnp.array([2.0, 2.5]).reshape(2, 1, 1)))
    out = composite_vdis(jnp.stack([v0.color, v1.color]),
                         jnp.stack([v0.depth, v1.depth]),
                         CompositeConfig(max_output_supersegments=4,
                                         adaptive=False))
    img = np.asarray(render_vdi_same_view(out))[:, 0, 0]
    # red (near, alpha .9) dominates
    assert img[0] > img[1]
    d = np.asarray(out.depth)[:, :, 0, 0]
    assert np.isclose(d[0, 0], 2.0, atol=1e-5)


def test_composite_empty_inputs():
    k = 3
    empty = VDI.empty(k, 2, 2)
    out = composite_vdis(jnp.stack([empty.color, empty.color]),
                         jnp.stack([empty.depth, empty.depth]))
    assert np.asarray(out.count).sum() == 0


def test_plain_composite_depth_order():
    # two full-screen images; nearer one (rank 1) must win
    img0 = jnp.zeros((4, 2, 2)).at[1].set(0.8).at[3].set(0.8)   # green
    img1 = jnp.zeros((4, 2, 2)).at[0].set(0.9).at[3].set(0.9)   # red
    d0 = jnp.full((2, 2), 5.0)
    d1 = jnp.full((2, 2), 1.0)
    out = np.asarray(composite_plain(jnp.stack([img0, img1]),
                                     jnp.stack([d0, d1])))
    assert (out[0] > out[1]).all()
    # alpha-under: total alpha = .9 + .1*.8
    assert np.allclose(out[3], 0.98, atol=1e-6)


def test_depth_min_composite():
    img0 = jnp.ones((4, 2, 2)) * 0.2
    img1 = jnp.ones((4, 2, 2)) * 0.7
    d0 = jnp.array([[1.0, 9.0], [1.0, 9.0]])
    d1 = jnp.array([[5.0, 2.0], [5.0, 2.0]])
    img, d = composite_depth_min(jnp.stack([img0, img1]),
                                 jnp.stack([d0, d1]))
    img, d = np.asarray(img), np.asarray(d)
    assert img[0, 0, 0] == np.float32(0.2) and img[0, 0, 1] == np.float32(0.7)
    assert d[0, 0] == 1.0 and d[0, 1] == 2.0


def test_n1_composite_is_identity_pad():
    """N=1 with K_out >= K and the default backend: the composite's
    defined behavior is the verbatim input padded with empty slots (the
    merge fold's search floor would re-merge for no gain) — and it must
    render like the real fold, which explicit backends still run."""
    from scenery_insitu_tpu.config import VDIConfig
    from scenery_insitu_tpu.core.camera import Camera
    from scenery_insitu_tpu.core.transfer import for_dataset
    from scenery_insitu_tpu.core.vdi import render_vdi_same_view
    from scenery_insitu_tpu.core.volume import procedural_volume
    from scenery_insitu_tpu.ops.vdi_gen import generate_vdi
    from scenery_insitu_tpu.utils.image import psnr

    vol = procedural_volume(32, kind="blobs", seed=5)
    tf = for_dataset("procedural")
    cam = Camera.create((0.1, 0.4, 2.8), fov_y_deg=45.0, near=0.3, far=12.0)
    vdi, _ = generate_vdi(vol, tf, cam, 48, 40,
                          VDIConfig(max_supersegments=8, adaptive_iters=3),
                          max_steps=96)

    out = composite_vdis(vdi.color[None], vdi.depth[None],
                         CompositeConfig(max_output_supersegments=10))
    np.testing.assert_array_equal(np.asarray(out.color[:8]),
                                  np.asarray(vdi.color))
    np.testing.assert_array_equal(np.asarray(out.depth[:8]),
                                  np.asarray(vdi.depth))
    assert float(out.color[8:, 3].max()) == 0.0     # padding is empty
    assert np.isinf(np.asarray(out.depth[8:])).all()

    # an explicitly requested backend still runs the real merge fold, and
    # the two stay visually equivalent
    slow = composite_vdis(vdi.color[None], vdi.depth[None],
                          CompositeConfig(max_output_supersegments=10,
                                          backend="xla"))
    a = render_vdi_same_view(out)
    b = render_vdi_same_view(slow)
    q = psnr(np.asarray(b), np.asarray(a))
    assert q > 40.0, f"PSNR {q:.1f} dB"


def _stream(m: int, seed: int, h: int = 12, w: int = 20):
    """A stacked stream as the exchange delivers it: ~40 % empty slots at
    +inf carrying stale colours, whole pixels empty, and two slot rows
    with identical start depths (rows 2 and 9, distinct payloads)."""
    rng = np.random.default_rng(seed)
    start = rng.uniform(0.5, 9.0, (m, h, w)).astype(np.float32)
    start[9] = start[2]
    start[rng.random((m, h, w)) < 0.4] = np.inf
    start[:, :2, :5] = np.inf                         # whole pixels empty
    end = np.where(np.isinf(start), np.inf, start + 0.07).astype(np.float32)
    color = rng.uniform(0.01, 1.0, (m, 4, h, w)).astype(np.float32)
    return jnp.asarray(color), jnp.asarray(np.stack([start, end], axis=1))


@pytest.mark.parametrize("m", [16, 32, 64])
def test_sort_stream_bit_equal_to_argsort_gather(m):
    """`sort_stream` carries the payload through its one stable sort; the
    result is bit-equal to sorting a permutation and gathering with it
    (the form it replaced): same order, ties in input order, +inf depths
    intact, stale colours of empty slots zeroed."""
    color, depth = _stream(m, seed=m)

    order = jnp.argsort(depth[:, 0], axis=0)          # stable
    want_c = jnp.take_along_axis(color, order[:, None], axis=0)
    want_d = jnp.take_along_axis(depth, order[:, None], axis=0)
    want_c = jnp.where(jnp.isfinite(want_d[:, 0])[:, None], want_c, 0.0)

    got_c, got_d = jax.jit(sort_stream)(color, depth)
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))

    got_c, got_d = np.asarray(got_c), np.asarray(got_d)
    assert np.isinf(got_d[:, :, :2, :5]).all()        # empty pixels stay so
    assert (np.moveaxis(got_c, 1, -1)[np.isinf(got_d[:, 0])] == 0.0).all()
    # the tie: wherever rows 2 and 9 are both live they are adjacent in the
    # output, row 2's payload first
    c_in, d_in = np.asarray(color), np.asarray(depth)
    both = np.isfinite(d_in[2, 0]) & np.isfinite(d_in[9, 0])
    assert both.any()
    first = np.argmax(got_d[:, 0] == d_in[2, 0][None], axis=0)   # [h, w]
    ys, xs = np.nonzero(both)
    np.testing.assert_array_equal(got_c[first[ys, xs], :, ys, xs],
                                  c_in[2][:, ys, xs].T)
    np.testing.assert_array_equal(got_c[first[ys, xs] + 1, :, ys, xs],
                                  c_in[9][:, ys, xs].T)


def test_composite_n4_lowers_without_gather():
    """The four-rank merge applies no permutation: the lowered program of
    `composite_vdis` for n = 4 holds one multi-operand sort (key + five
    payload planes) and no gather."""
    colors = jax.ShapeDtypeStruct((4, 16, 4, 16, 128), jnp.float32)
    depths = jax.ShapeDtypeStruct((4, 16, 2, 16, 128), jnp.float32)
    cfg = CompositeConfig(max_output_supersegments=16)
    text = jax.jit(lambda c, d: composite_vdis(c, d, cfg)).lower(
        colors, depths).as_text()
    assert "gather" not in text
    sorts = [ln for ln in text.splitlines() if '"stablehlo.sort"(' in ln]
    assert len(sorts) == 1 and "is_stable = true" in sorts[0]
    operands = sorts[0].split('"stablehlo.sort"(')[1].split(")")[0]
    assert len(operands.split(",")) == 6
