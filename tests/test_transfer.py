import jax.numpy as jnp
import numpy as np

from scenery_insitu_tpu.core.transfer import (TransferFunction, colormap_lut,
                                              for_dataset)


def test_ramp_endpoints():
    tf = TransferFunction.ramp(0.2, 0.8, max_alpha=0.5)
    _, a0 = tf(jnp.array(0.1))
    _, a1 = tf(jnp.array(0.9))
    _, amid = tf(jnp.array(0.5))
    assert float(a0) < 1e-3
    assert np.isclose(float(a1), 0.5, atol=1e-2)
    assert np.isclose(float(amid), 0.25, atol=1e-2)


def test_points_interpolation():
    tf = TransferFunction.points([(0.0, 0.0), (0.5, 1.0), (1.0, 0.0)])
    _, a = tf(jnp.array([0.25, 0.5, 0.75]))
    assert np.allclose(np.asarray(a), [0.5, 1.0, 0.5], atol=2e-2)


def test_colormaps_shapes_and_range():
    for name in ["grays", "hot", "jet", "viridis"]:
        lut = colormap_lut(name)
        assert lut.shape == (256, 3)
        assert lut.min() >= 0.0 and lut.max() <= 1.0


def test_dataset_tfs_exist():
    for name in ["kingsnake", "beechnut", "simulation", "rayleigh_taylor",
                 "gray_scott", "unknown_falls_back"]:
        tf = for_dataset(name)
        rgb, a = tf(jnp.array(0.5))
        assert rgb.shape == (3,)


def test_batched_sampling():
    tf = TransferFunction.ramp(0.0, 1.0)
    rgb, a = tf(jnp.linspace(0, 1, 7).reshape(7, 1) * jnp.ones((7, 3)))
    assert rgb.shape == (7, 3, 3) and a.shape == (7, 3)


def test_colour_sum_is_lowered_at_f32_precision():
    """PR 46: a TPU's MXU rounds an f32 dot's operands to bf16 at the
    default precision (colours 3.6e-3 off the polyline on a v5e); the
    fold kernel that shades in VMEM sums the same knots in f32, and the
    chip benchmark holds the two paths to 120 dB of each other. So the
    colour sum asks for the highest precision, in every lowering."""
    import jax

    tf = for_dataset("gray_scott")
    text = jax.jit(lambda x: tf(x)[0]).lower(jnp.zeros((8, 128))).as_text()
    dots = [ln for ln in text.splitlines() if "dot_general" in ln]
    assert len(dots) == 1 and "HIGHEST" in dots[0]
    # and what it computes is the polyline, to f32 rounding
    x = np.linspace(0.0, 1.0, 1001)
    rgb, _ = tf(jnp.asarray(x, jnp.float32))
    want = np.asarray(tf.color_b, np.float64) + np.maximum(
        x[:, None] - np.asarray(tf.color_x, np.float64), 0.0) \
        @ np.asarray(tf.color_m, np.float64)
    np.testing.assert_allclose(np.asarray(rgb), want, atol=2e-6)
