"""Golden-fixture regression tests: re-render the committed configs of
tests/golden/make_golden.py and compare against the committed images /
VDI arrays. A kernel change that shifts output breaks one of these with
the config name in the message (the mechanical version of the
reference's dump→reload→look-at-it validation loop, SURVEY.md §4.2).

Also pins the Vulkan reference-frame normalization protocol
(ops/vdi_convert: gamma / projection fix / y-flip) with exact unit
checks — the day a Vulkan render of the reference exists, comparing it
against `to_reference_frame(ours)` by PSNR is the whole procedure
(documented in PARITY.md)."""

import os

import numpy as np
import pytest

from tests.golden.make_golden import GOLDEN_DIR, build_all

_CACHE = {}


def _rendered():
    if "out" not in _CACHE:
        _CACHE["out"] = build_all(out_dir=None)
    return _CACHE["out"]


def _load_png(name):
    from PIL import Image

    return np.asarray(Image.open(
        os.path.join(GOLDEN_DIR, f"golden_{name}.png")), np.float32)


def _to_png_space(img_chw, gamma=2.2):
    from scenery_insitu_tpu.utils.image import to_display

    return np.asarray(to_display(np.asarray(img_chw), gamma), np.float32)


# reference_frame is already gamma-encoded by to_reference_frame, so its
# PNG round trip uses gamma=1.0 (exactly one encode in the stored pixels)
_PNG_GAMMA = {"reference_frame": 1.0}


@pytest.mark.parametrize("name", ["raycast_gather", "raycast_mxu",
                                  "vdi_decode", "novel_view",
                                  "vdi_gather_decode", "reference_frame"])
def test_golden_image(name):
    got = _to_png_space(_rendered()[name], _PNG_GAMMA.get(name, 2.2))
    want = _load_png(name)
    assert got.shape == want.shape, (
        f"{name}: shape {got.shape} != committed {want.shape}")
    # 8-bit space: tiny FP drift tolerated, real regressions are far above
    maxdiff = float(np.abs(got - want).max())
    assert maxdiff <= 3.0, (
        f"golden image {name!r} drifted: max 8-bit diff {maxdiff:.1f} "
        "(if the change is intentional, regenerate via "
        "tests/golden/make_golden.py and commit)")


def test_golden_vdi_arrays():
    out = _rendered()
    with np.load(os.path.join(GOLDEN_DIR, "golden_vdi.npz")) as z:
        np.testing.assert_allclose(out["vdi_color"], z["color"],
                                   rtol=1e-4, atol=1e-5,
                                   err_msg="composited VDI color drifted")
        got_d, want_d = out["vdi_depth"], z["depth"]
        live = np.isfinite(want_d)
        assert (np.isfinite(got_d) == live).all(), "VDI slot liveness"
        np.testing.assert_allclose(got_d[live], want_d[live],
                                   rtol=1e-4, atol=1e-5,
                                   err_msg="composited VDI depth drifted")


def test_pallas_fold_matches_golden():
    """The Pallas fold schedule must reproduce the committed (XLA-fold)
    VDI fixture — pins schedule-independence to a committed artifact.
    Shares make_golden.build_vdi so the configs cannot drift apart."""
    from tests.golden.make_golden import build_vdi

    comp, _, _ = build_vdi(fold="pallas_fused")
    with np.load(os.path.join(GOLDEN_DIR, "golden_vdi.npz")) as z:
        np.testing.assert_allclose(np.asarray(comp.color), z["color"],
                                   rtol=2e-4, atol=2e-5)


# ------------------------- Vulkan-convention converters (exact semantics)


def test_vulkan_projection_fix_semantics():
    """fix @ P maps GL NDC (y up, z in [-1,1]) to Vulkan NDC (y down,
    z in [0,1]) — the matrix of DistributedVolumes.kt:67-79."""
    import jax.numpy as jnp

    from scenery_insitu_tpu.core.camera import Camera, projection_matrix
    from scenery_insitu_tpu.ops.vdi_convert import (projection_gl_to_vulkan,
                                                    projection_vulkan_to_gl)

    cam = Camera.create((0.2, 0.4, 3.0), fov_y_deg=50.0, near=0.5, far=10.0)
    p_gl = projection_matrix(cam, 64, 48)
    p_vk = projection_gl_to_vulkan(p_gl)

    def ndc(p, v):
        c = np.asarray(p @ jnp.asarray(v, jnp.float32))
        return c[:3] / c[3]

    for point in ([0.1, 0.2, -0.6, 1.0], [-0.3, 0.1, -5.0, 1.0]):
        g = ndc(p_gl, point)
        v = ndc(p_vk, point)
        np.testing.assert_allclose(v[0], g[0], rtol=1e-6)        # x same
        np.testing.assert_allclose(v[1], -g[1], rtol=1e-6)       # y flipped
        np.testing.assert_allclose(v[2], (g[2] + 1.0) / 2.0,     # z [0,1]
                                   rtol=1e-5)
        assert 0.0 <= v[2] <= 1.0
    # exact round trip
    np.testing.assert_allclose(np.asarray(projection_vulkan_to_gl(p_vk)),
                               np.asarray(p_gl), atol=1e-6)


def test_gamma_and_flip_roundtrip():
    from scenery_insitu_tpu.ops.vdi_convert import (flip_y, gamma_decode,
                                                    gamma_encode,
                                                    to_reference_frame)

    rng = np.random.default_rng(0)
    img = rng.random((4, 8, 6)).astype(np.float32)
    enc = np.asarray(gamma_encode(img))
    # alpha untouched, rgb = v^(1/2.2)
    np.testing.assert_allclose(enc[3], img[3])
    np.testing.assert_allclose(enc[:3], img[:3] ** (1 / 2.2), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gamma_decode(enc)), img,
                               rtol=1e-4, atol=1e-6)
    flipped = np.asarray(flip_y(img))
    np.testing.assert_array_equal(flipped, img[:, ::-1, :])
    ref = np.asarray(to_reference_frame(img))
    np.testing.assert_allclose(ref, np.asarray(flip_y(gamma_encode(img))))
