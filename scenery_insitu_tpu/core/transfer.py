"""Transfer functions and colormaps — gather-free on TPU.

The reference builds per-dataset piecewise-linear opacity ramps and colormaps
(scenery ``TransferFunction.ramp`` + ``Colormap``; reference
DistributedVolumes.kt:179-219, VolumeFromFileExample.kt:405-455) and samples
them through GPU texture hardware. A texture lookup is a *gather*, and the
slice-march hot loop evaluates the transfer function ~26M times per frame —
profiled on a v5e, LUT gathers were 96% of the march cost (584 ms vs 22 ms
without them). TPUs have no texture units, so here a transfer function is
stored directly as its piecewise-linear *knot form* and evaluated as a
relu-sum::

    f(x) = base + sum_i  m_i * relu(x - x_i)

(x_i = knot positions, m_i = slope *changes* at the knots) — a handful of
fully-vectorizable elementwise ops on the VPU, zero gathers, exact for the
polyline the control points define. Knot arrays are padded to a fixed
MAX_KNOTS so every TF shares one pytree structure (one jit cache entry).
Dense LUT views remain available as properties for host-side use
(serialization, plotting).
"""

from __future__ import annotations

from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LUT_SIZE = 256
MAX_KNOTS = 16


def _relu_terms(xs: np.ndarray, ys: np.ndarray):
    """Knot form (x, slope-deltas, base) of the clamped piecewise-linear
    interpolant through (xs, ys): f equals np.interp(x, xs, ys) on [0, 1]."""
    xs = np.asarray(xs, np.float32)
    ys = np.asarray(ys, np.float32)
    slopes = np.diff(ys) / np.maximum(np.diff(xs), 1e-6)
    s_in = np.concatenate([[0.0], slopes]).astype(np.float32)
    s_out = np.concatenate([slopes, [0.0]]).astype(np.float32)
    deltas = s_out - s_in
    # value at x=0 with all relu terms inactive = left-clamped value
    return xs, deltas, np.float32(ys[0])


def _pad(x: np.ndarray, fill: float) -> np.ndarray:
    out = np.full(MAX_KNOTS, fill, np.float32)
    out[: len(x)] = x
    return out


def _pad2(x: np.ndarray) -> np.ndarray:
    out = np.zeros((MAX_KNOTS, x.shape[1]), np.float32)
    out[: len(x)] = x
    return out


class TransferFunction(NamedTuple):
    """Maps normalized scalar value [0,1] -> (rgb, alpha). Knot form; see
    module docstring. Inactive (padding) knots sit at x=2 with zero slope."""

    alpha_x: jnp.ndarray   # f32[MAX_KNOTS] alpha knot positions
    alpha_m: jnp.ndarray   # f32[MAX_KNOTS] alpha slope deltas
    alpha_b: jnp.ndarray   # f32[]          alpha at x=0
    color_x: jnp.ndarray   # f32[MAX_KNOTS] color knot positions
    color_m: jnp.ndarray   # f32[MAX_KNOTS, 3] per-channel slope deltas
    color_b: jnp.ndarray   # f32[3]         rgb at x=0

    @classmethod
    def from_polylines(cls, alpha_pts: Sequence[Tuple[float, float]],
                       color_xs: np.ndarray, color_rgb: np.ndarray
                       ) -> "TransferFunction":
        alpha_pts = sorted(alpha_pts)
        if len(alpha_pts) > MAX_KNOTS - 1:
            raise ValueError(f"at most {MAX_KNOTS - 1} alpha control points")
        ax, am, ab = _relu_terms(np.array([p[0] for p in alpha_pts]),
                                 np.array([p[1] for p in alpha_pts]))
        cx, _, _ = _relu_terms(color_xs, color_rgb[:, 0])
        cms = np.stack([_relu_terms(color_xs, color_rgb[:, c])[1]
                        for c in range(3)], axis=-1)
        return cls(jnp.asarray(_pad(ax, 2.0)), jnp.asarray(_pad(am, 0.0)),
                   jnp.float32(ab),
                   jnp.asarray(_pad(cx, 2.0)), jnp.asarray(_pad2(cms)),
                   jnp.asarray(color_rgb[0], jnp.float32))

    @classmethod
    def ramp(cls, low: float = 0.0, high: float = 1.0, max_alpha: float = 1.0,
             colormap: str = "grays") -> "TransferFunction":
        """Opacity 0 below `low`, linear to `max_alpha` at `high`
        (≅ scenery TransferFunction.ramp used at DistributedVolumes.kt:183)."""
        high = max(high, low + 1e-6)
        xs, rgb = colormap_polyline(colormap)
        return cls.from_polylines([(low, 0.0), (high, max_alpha)], xs, rgb)

    @classmethod
    def points(cls, pts: Sequence[Tuple[float, float]],
               colormap: str = "grays") -> "TransferFunction":
        """Piecewise-linear opacity through (value, alpha) control points
        (≅ the addControlPoint chains, DistributedVolumes.kt:187-217)."""
        xs, rgb = colormap_polyline(colormap)
        return cls.from_polylines(pts, xs, rgb)

    def __call__(self, value: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Sample -> (rgb f32[..., 3], alpha f32[...]). Gather-free."""
        x = jnp.clip(value, 0.0, 1.0)[..., None]
        a = self.alpha_b + jnp.sum(
            self.alpha_m * jnp.maximum(x - self.alpha_x, 0.0), axis=-1)
        tc = jnp.maximum(x - self.color_x, 0.0)           # [..., K]
        # an f32 sum like alpha's: at a TPU's default precision the MXU
        # rounds both operands to bf16 (colours 3.6e-3 off the polyline,
        # measured on a v5e), and the fold kernel that shades in VMEM
        # (ops/pallas_seg._shade_plane) evaluates these knots in f32
        rgb = self.color_b + jnp.tensordot(
            tc, self.color_m, axes=([-1], [0]),
            precision=jax.lax.Precision.HIGHEST)
        return rgb, a

    # ------------------------------------------------ dense LUT views (host)
    @property
    def alpha_lut(self) -> jnp.ndarray:
        """f32[LUT_SIZE] dense sampling (serialization / plotting)."""
        return self(jnp.linspace(0.0, 1.0, LUT_SIZE))[1]

    @property
    def color_lut(self) -> jnp.ndarray:
        """f32[LUT_SIZE, 3] dense sampling."""
        return self(jnp.linspace(0.0, 1.0, LUT_SIZE))[0]

    def max_alpha_in(self, lo: jnp.ndarray, hi: jnp.ndarray) -> jnp.ndarray:
        """Max alpha over value interval(s) [lo, hi] (same leading shape) —
        the conservative bound the occupancy/empty-space-skip machinery needs
        (a slab whose value range maps to zero alpha everywhere can be
        skipped even under interpolation, because interpolated values stay
        inside the slab's [min, max])."""
        lo = jnp.clip(lo, 0.0, 1.0)[..., None]
        hi = jnp.clip(hi, 0.0, 1.0)[..., None]
        ends = jnp.concatenate([
            self.alpha_b + jnp.sum(
                self.alpha_m * jnp.maximum(lo - self.alpha_x, 0.0), -1,
                keepdims=True),
            self.alpha_b + jnp.sum(
                self.alpha_m * jnp.maximum(hi - self.alpha_x, 0.0), -1,
                keepdims=True)], axis=-1)
        # interior maxima can only sit at knots inside (lo, hi);
        # alpha at knot j = base + sum_i m_i * relu(x_j - x_i)
        knot_vals = self.alpha_b + jnp.sum(
            self.alpha_m * jnp.maximum(self.alpha_x[:, None]
                                       - self.alpha_x[None, :], 0.0), -1)
        inside = (self.alpha_x >= lo) & (self.alpha_x <= hi)
        interior = jnp.max(jnp.where(inside, knot_vals, -jnp.inf), axis=-1)
        return jnp.maximum(jnp.max(ends, axis=-1), interior)


def opacity_edges(tf: TransferFunction, eps: float = 1e-4) -> np.ndarray:
    """Sorted f32[M] positions of the TF's ACTIVE opacity knots — where
    the alpha polyline changes slope — host-side (numpy). This is the
    edge set of the LOD planner's TF-straddle coarsening gate
    (`parallel.lod.select_levels`; docs/PERF.md "LOD marching"): pooling
    a brick whose value range crosses one of these positions averages
    across an opacity feature and can erase or invent it, so such bricks
    must stay level 0. Padding knots (x = 2, zero slope) and knots whose
    |slope delta| <= ``eps`` carry no feature and are dropped."""
    x = np.asarray(tf.alpha_x, np.float32)
    m = np.asarray(tf.alpha_m, np.float32)
    keep = (x <= 1.0) & (np.abs(m) > eps)
    return np.sort(x[keep])


def colormap_polyline(name: str) -> Tuple[np.ndarray, np.ndarray]:
    """Built-in colormaps as exact piecewise-linear polylines
    (xs f32[K], rgb f32[K, 3]) (≅ scenery Colormap.get, used with
    "hot"/"jet"/"grays" at VolumeFromFileExample.kt:399-403)."""
    if name == "grays":
        xs = np.array([0.0, 1.0], np.float32)
        rgb = np.array([[0, 0, 0], [1, 1, 1]], np.float32)
    elif name == "hot":
        xs = np.array([0.0, 1 / 3, 2 / 3, 1.0], np.float32)
        rgb = np.array([[0, 0, 0], [1, 0, 0], [1, 1, 0], [1, 1, 1]],
                       np.float32)
    elif name == "jet":
        # every kink of clip(1.5-|4x-c|, 0, 1) for c=3,2,1 lies on the k/8
        # grid, so sampling there reproduces the formula exactly
        xs = np.linspace(0.0, 1.0, 9, dtype=np.float32)
        r = np.clip(1.5 - np.abs(4 * xs - 3), 0, 1)
        g = np.clip(1.5 - np.abs(4 * xs - 2), 0, 1)
        b = np.clip(1.5 - np.abs(4 * xs - 1), 0, 1)
        rgb = np.stack([r, g, b], -1).astype(np.float32)
    elif name == "viridis":
        # 11-anchor approximation of matplotlib viridis
        rgb = np.array([
            [0.267, 0.005, 0.329], [0.283, 0.141, 0.458],
            [0.254, 0.265, 0.530], [0.207, 0.372, 0.553],
            [0.164, 0.471, 0.558], [0.128, 0.567, 0.551],
            [0.135, 0.659, 0.518], [0.267, 0.749, 0.441],
            [0.478, 0.821, 0.318], [0.741, 0.873, 0.150],
            [0.993, 0.906, 0.144]], np.float32)
        xs = np.linspace(0.0, 1.0, len(rgb), dtype=np.float32)
    else:
        raise ValueError(f"unknown colormap {name!r}")
    return xs, rgb


def colormap_lut(name: str, n: int = LUT_SIZE) -> np.ndarray:
    """Dense f32[n, 3] sampling of a built-in colormap (host-side users:
    particle splat color tables, previews)."""
    xs, rgb = colormap_polyline(name)
    x = np.linspace(0.0, 1.0, n, dtype=np.float32)
    return np.stack([np.interp(x, xs, rgb[:, c]) for c in range(3)],
                    -1).astype(np.float32)


# Per-dataset transfer functions mirroring the reference's hand-tuned tables
# (VolumeFromFileExample.kt:405-455, DistributedVolumes.kt:179-219).
DATASET_TRANSFER_FUNCTIONS = {
    "kingsnake": lambda: TransferFunction.points(
        [(0.0, 0.0), (0.43, 0.0), (0.5, 0.005)], "grays"),
    "beechnut": lambda: TransferFunction.points(
        [(0.0, 0.0), (0.43, 0.0), (0.457, 0.321), (0.494, 0.0), (1.0, 0.0)], "grays"),
    "simulation": lambda: TransferFunction.points(
        [(0.0, 0.0), (0.1, 0.0), (0.15, 0.1), (0.22, 0.05), (1.0, 0.1)], "hot"),
    "rayleigh_taylor": lambda: TransferFunction.points(
        [(0.0, 0.3), (0.3, 0.05), (0.5, 0.0), (0.7, 0.05), (1.0, 0.3)], "jet"),
    "rotstrat": lambda: TransferFunction.ramp(0.0, 1.0, 0.4, "jet"),
    "procedural": lambda: TransferFunction.ramp(0.05, 0.8, 0.5, "hot"),
    "gray_scott": lambda: TransferFunction.points(
        [(0.0, 0.0), (0.12, 0.0), (0.3, 0.12), (0.65, 0.3), (1.0, 0.5)], "viridis"),
    # vorticity-magnitude fields (vortex sim + the hybrid tracer mode render
    # the same field, so the session and the single-chip Config 5 pipeline
    # must agree on one TF)
    "vortex": lambda: TransferFunction.ramp(0.0, 1.0, 0.4, "jet"),
    "hybrid": lambda: TransferFunction.ramp(0.0, 1.0, 0.4, "jet"),
    # particle sims render sort-first splats and never consult the TF,
    # but the session still constructs one — registering them keeps a
    # REGISTERED scenario (scenery_insitu_tpu/scenarios) off the
    # unknown-dataset ledger
    "lennard_jones": lambda: TransferFunction.ramp(0.05, 0.8, 0.5, "hot"),
    "sho": lambda: TransferFunction.ramp(0.05, 0.8, 0.5, "hot"),
}


def for_dataset(name: str) -> TransferFunction:
    try:
        return DATASET_TRANSFER_FUNCTIONS[name.lower()]()
    except KeyError:
        # an unknown dataset renders with the generic gray ramp — a real
        # behavior change (a typo'd runtime.dataset silently loses the
        # tuned TF), so it lands on the fallback ledger
        from scenery_insitu_tpu import obs

        obs.degrade("core.dataset_tf", name, "grays_ramp",
                    f"no tuned transfer function for dataset {name!r} "
                    f"(known: {sorted(DATASET_TRANSFER_FUNCTIONS)})")
        return TransferFunction.ramp(0.05, 0.8, 0.5, "grays")
