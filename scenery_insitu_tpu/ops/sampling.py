"""Volume sampling primitives (≅ the ``sampleVolume``/``Convert`` shader
segments scenery injects into the raycasters — reference
VDIGenerator.comp:259-261 and AccumulateVDI.comp:4)."""

from __future__ import annotations

import jax.numpy as jnp

from scenery_insitu_tpu.core.volume import Volume, value_scale


def sample_trilinear(data: jnp.ndarray, pos_xyz: jnp.ndarray) -> jnp.ndarray:
    """Trilinearly sample ``data f32[D, H, W]`` at continuous voxel
    coordinates ``pos_xyz f32[..., 3]`` (x, y, z; voxel centers at
    integer + 0.5). Coordinates are clamped to the border (GL
    CLAMP_TO_EDGE semantics, matching the reference's samplers). A field
    at a raw file's integer dtype is sampled as stored and the result
    scaled to [0, 1] (`core.volume.value_scale`)."""
    d, h, w = data.shape
    p = pos_xyz - 0.5
    x = jnp.clip(p[..., 0], 0.0, w - 1.0)
    y = jnp.clip(p[..., 1], 0.0, h - 1.0)
    z = jnp.clip(p[..., 2], 0.0, d - 1.0)

    x0 = jnp.clip(jnp.floor(x).astype(jnp.int32), 0, w - 2) if w > 1 else jnp.zeros_like(x, jnp.int32)
    y0 = jnp.clip(jnp.floor(y).astype(jnp.int32), 0, h - 2) if h > 1 else jnp.zeros_like(y, jnp.int32)
    z0 = jnp.clip(jnp.floor(z).astype(jnp.int32), 0, d - 2) if d > 1 else jnp.zeros_like(z, jnp.int32)
    fx = x - x0
    fy = y - y0
    fz = z - z0

    flat = data.reshape(-1)
    x1 = jnp.minimum(x0 + 1, w - 1)
    y1 = jnp.minimum(y0 + 1, h - 1)
    z1 = jnp.minimum(z0 + 1, d - 1)

    def at(zi, yi, xi):
        # gather in storage dtype (bf16 render copies keep their halved
        # HBM traffic), accumulate the lerp in f32
        return jnp.take(flat, (zi * h + yi) * w + xi).astype(jnp.float32)

    c000 = at(z0, y0, x0)
    c001 = at(z0, y0, x1)
    c010 = at(z0, y1, x0)
    c011 = at(z0, y1, x1)
    c100 = at(z1, y0, x0)
    c101 = at(z1, y0, x1)
    c110 = at(z1, y1, x0)
    c111 = at(z1, y1, x1)

    c00 = c000 * (1 - fx) + c001 * fx
    c01 = c010 * (1 - fx) + c011 * fx
    c10 = c100 * (1 - fx) + c101 * fx
    c11 = c110 * (1 - fx) + c111 * fx
    c0 = c00 * (1 - fy) + c01 * fy
    c1 = c10 * (1 - fy) + c11 * fy
    out = c0 * (1 - fz) + c1 * fz
    scale = value_scale(data.dtype)
    return out if scale == 1.0 else out * jnp.float32(scale)


def sample_volume_world(vol: Volume, world_pos: jnp.ndarray) -> jnp.ndarray:
    """Sample a Volume at world positions ``f32[..., 3]`` (x, y, z)."""
    return sample_trilinear(vol.data, vol.world_to_voxel(world_pos))


def intersect_aabb(origin: jnp.ndarray, dirs: jnp.ndarray,
                   box_min: jnp.ndarray, box_max: jnp.ndarray):
    """Slab-method ray/AABB intersection (≅ intersectBoundingBox,
    VDIGenerator.comp:333-347).

    origin f32[3], dirs f32[3, ...]; returns (tnear, tfar) each f32[...];
    a miss yields tnear > tfar."""
    inv = 1.0 / jnp.where(jnp.abs(dirs) < 1e-12,
                          jnp.where(dirs < 0, -1e-12, 1e-12), dirs)
    o = origin.reshape((3,) + (1,) * (dirs.ndim - 1))
    t0 = (box_min.reshape(o.shape) - o) * inv
    t1 = (box_max.reshape(o.shape) - o) * inv
    tmin = jnp.minimum(t0, t1)
    tmax = jnp.maximum(t0, t1)
    tnear = jnp.max(tmin, axis=0)
    tfar = jnp.min(tmax, axis=0)
    return jnp.maximum(tnear, 0.0), tfar


def adjust_opacity(alpha: jnp.ndarray, length_ratio) -> jnp.ndarray:
    """Opacity correction for a sampling interval whose length differs from
    the nominal one: ``1 - (1 - a)^ratio`` (≅ adjustOpacity,
    VDIGenerator.comp:80-82)."""
    return 1.0 - jnp.power(jnp.clip(1.0 - alpha, 1e-7, 1.0), length_ratio)
