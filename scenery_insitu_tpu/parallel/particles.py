"""Distributed particle rendering: sort-first compositing over the mesh.

The reference's particle mode shards particles by compute rank (OpenFPM
domain decomposition), renders each rank's spheres locally, and min-depth
composites full images on a head node (reference InVisRenderer.kt +
Head.kt:98-134, NaiveCompositor.frag:15-28). Here the same shape is one
jitted shard_map program: per-rank splat, ``all_gather`` of the small
image+depth pair over ICI, per-pixel depth-min select.

Coloring uses globally psum-reduced speed statistics so the distributed
render matches a single-device render of the full particle set (tests
assert this, tests/test_splat.py).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from scenery_insitu_tpu.core.camera import Camera
from scenery_insitu_tpu.ops.composite import composite_depth_min
from scenery_insitu_tpu.ops.splat import (SplatOutput, speed_colors,
                                          splat_particles)
from jax import shard_map


def sort_first_splat(pos, vel, axis: str, width: int, height: int,
                     radius, stamp: int = 9, colormap: str = "jet",
                     cam: Optional[Camera] = None, view=None, proj=None
                     ) -> SplatOutput:
    """The per-rank body of sort-first particle rendering (call inside
    shard_map): speed-color with globally psum-reduced statistics (the
    reference computes these over the full population too,
    InVisRenderer.kt:166-175), splat this rank's spheres, all_gather the
    small image+depth pair, per-pixel depth-min. Returns a replicated
    SplatOutput. Shared by the particle and hybrid pipelines."""
    speed = jnp.linalg.norm(vel, axis=-1)
    cnt = jax.lax.psum(jnp.float32(speed.shape[0]), axis)
    s1 = jax.lax.psum(jnp.sum(speed), axis)
    s2 = jax.lax.psum(jnp.sum(speed * speed), axis)
    mean = s1 / cnt
    std = jnp.sqrt(jnp.maximum(s2 / cnt - mean * mean, 0.0))

    rgba = speed_colors(vel, colormap, mean=mean, std=std)
    out = splat_particles(pos, rgba, radius, cam, width, height, stamp,
                          view=view, proj=proj)
    imgs = jax.lax.all_gather(out.image, axis)              # [n, 4, H, W]
    deps = jax.lax.all_gather(out.depth, axis)              # [n, H, W]
    img, dep = composite_depth_min(imgs, deps)
    return SplatOutput(img, dep)


def distributed_particle_step(mesh: Mesh, width: int, height: int,
                              radius: float = 0.01, stamp: int = 9,
                              colormap: str = "jet",
                              axis_name: Optional[str] = None):
    """Build the jitted distributed particle render step.

    Returns ``f(pos f32[N, 3] (sharded on N), vel f32[N, 3] (same), cam
    Camera) -> SplatOutput`` with replicated full-frame image [4, H, W] +
    depth [H, W]. N must divide by the mesh size.
    """
    axis = axis_name or mesh.axis_names[0]

    def step(pos, vel, cam: Camera) -> SplatOutput:
        return sort_first_splat(pos, vel, axis, width, height, radius,
                                stamp, colormap, cam=cam)

    spec_part = P(axis, None)
    f = shard_map(step, mesh=mesh, in_specs=(spec_part, spec_part, P()),
                  out_specs=SplatOutput(P(), P()), check_vma=False)
    return jax.jit(f)


def shard_particles(arr: jnp.ndarray, mesh: Mesh,
                    axis_name: Optional[str] = None) -> jnp.ndarray:
    """Place a particle array [N, ...] onto the mesh sharded over N."""
    axis = axis_name or mesh.axis_names[0]
    return jax.device_put(arr, NamedSharding(mesh, P(axis)))
