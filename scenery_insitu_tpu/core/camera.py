"""Camera model and ray generation.

One convention, chosen once: right-handed OpenGL camera (looks down -z in eye
space), NDC z in [-1, 1], image row 0 at the *top* of the screen. The
reference needed a "Vulkan projection fix" matrix and a y-flip scattered
through shaders (reference DistributedVolumes.kt:67-79, ConvertToNDC.comp:238);
here rays are generated directly from the inverse view-projection, exactly as
VDIGenerator.comp:289 does with ``ipv = InverseView * InverseProjection``.

Supersegment/fragment depths throughout the framework are the world-space ray
parameter ``t`` (unit-length directions), NOT NDC z — see package docstring.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax.numpy as jnp
import numpy as np


class Camera(NamedTuple):
    """Pinhole camera. All leaves are jnp arrays so Camera is a jit-friendly
    pytree (≅ the camera pose + projection the reference passes in VDIData:
    DistributedVolumes.kt:706-716)."""

    eye: jnp.ndarray        # f32[3] world-space position
    target: jnp.ndarray     # f32[3] look-at point
    up: jnp.ndarray         # f32[3]
    fov_y: jnp.ndarray      # f32[] vertical field of view, radians
    near: jnp.ndarray       # f32[]
    far: jnp.ndarray        # f32[]

    @classmethod
    def create(cls, eye, target=(0.0, 0.0, 0.0), up=(0.0, 1.0, 0.0),
               fov_y_deg: float = 50.0, near: float = 0.1, far: float = 1000.0
               ) -> "Camera":
        f32 = lambda v: jnp.asarray(v, jnp.float32)
        return cls(f32(eye), f32(target), f32(up),
                   f32(jnp.deg2rad(fov_y_deg)), f32(near), f32(far))


class HostPose(NamedTuple):
    """The eye and target of ONE camera object as the host f32 values its
    device leaves were made from. A camera that arrived as host floats (a
    steering message) keeps them beside it, so that its holder can decide
    on the host what depends on the pose (the march regime,
    runtime/session.camera_regime) without reading the device copy back.
    They belong to ``camera`` and to no other object: a holder whose
    camera is a different object (orbited, restored, assigned by a
    caller) has no host values for it."""

    camera: Camera
    eye: np.ndarray         # f32[3]
    target: np.ndarray      # f32[3]


def look_at(eye: jnp.ndarray, target: jnp.ndarray, up: jnp.ndarray) -> jnp.ndarray:
    """World -> eye 4x4 view matrix (OpenGL convention)."""
    fwd = _normalize(target - eye)
    right = _normalize(jnp.cross(fwd, up))
    true_up = jnp.cross(right, fwd)
    rot = jnp.stack([right, true_up, -fwd])           # rows
    trans = -rot @ eye
    view = jnp.eye(4, dtype=jnp.float32)
    view = view.at[:3, :3].set(rot)
    view = view.at[:3, 3].set(trans)
    return view


def perspective(fov_y: jnp.ndarray, aspect: float, near, far) -> jnp.ndarray:
    """OpenGL perspective projection, NDC z in [-1, 1]."""
    f = 1.0 / jnp.tan(fov_y / 2.0)
    near = jnp.asarray(near, jnp.float32)
    far = jnp.asarray(far, jnp.float32)
    proj = jnp.zeros((4, 4), jnp.float32)
    proj = proj.at[0, 0].set(f / aspect)
    proj = proj.at[1, 1].set(f)
    proj = proj.at[2, 2].set((far + near) / (near - far))
    proj = proj.at[2, 3].set(2.0 * far * near / (near - far))
    proj = proj.at[3, 2].set(-1.0)
    return proj


def frustum(l, r, b, t, n, f) -> jnp.ndarray:
    """Off-axis (glFrustum-style) OpenGL perspective projection from the
    near-plane window [l, r] x [b, t]; NDC z in [-1, 1]. All arguments may
    be traced scalars (the slice-march virtual camera rebuilds its frustum
    every frame, ops/slicer.py)."""
    l, r, b, t, n, f = (jnp.asarray(v, jnp.float32) for v in (l, r, b, t, n, f))
    zero = jnp.zeros_like(n)
    row0 = jnp.stack([2 * n / (r - l), zero, (r + l) / (r - l), zero])
    row1 = jnp.stack([zero, 2 * n / (t - b), (t + b) / (t - b), zero])
    row2 = jnp.stack([zero, zero, (f + n) / (n - f), 2 * f * n / (n - f)])
    row3 = jnp.stack([zero, zero, -jnp.ones_like(n), zero])
    return jnp.stack([row0, row1, row2, row3])


def view_matrix(cam: Camera) -> jnp.ndarray:
    return look_at(cam.eye, cam.target, cam.up)


def projection_matrix(cam: Camera, width: int, height: int) -> jnp.ndarray:
    return perspective(cam.fov_y, width / height, cam.near, cam.far)


def pixel_rays(cam: Camera, width: int, height: int
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-pixel world-space rays.

    Returns (origin f32[3], dirs f32[3, H, W]); dirs are unit length so the
    ray parameter t is world distance. Pixel centers; row 0 = top.
    ≅ VDIGenerator.comp:283-296 (ipv unproject of the NDC pixel).
    """
    view = view_matrix(cam)
    proj = projection_matrix(cam, width, height)
    inv_vp = jnp.linalg.inv(proj @ view)

    j = (jnp.arange(width, dtype=jnp.float32) + 0.5) / width * 2.0 - 1.0
    i = 1.0 - (jnp.arange(height, dtype=jnp.float32) + 0.5) / height * 2.0
    ndc_x, ndc_y = jnp.meshgrid(j, i, indexing="xy")      # [H, W]

    def unproject(z):
        ndc = jnp.stack([ndc_x, ndc_y,
                         jnp.full_like(ndc_x, z), jnp.ones_like(ndc_x)])  # [4,H,W]
        w = jnp.einsum("ab,bhw->ahw", inv_vp, ndc)
        return w[:3] / w[3:4]

    # Direction through the exactly-known eye and the near-plane point: the
    # f32 unprojection of the far plane (ndc z=+1) is badly conditioned
    # (division by w ~ 0), so near-minus-far directions drift ~1e-3.
    p_near = unproject(-1.0)
    dirs = _normalize(p_near - cam.eye.reshape(3, 1, 1), axis=0)
    return cam.eye, dirs


def world_to_ndc(point_w: jnp.ndarray, view: jnp.ndarray, proj: jnp.ndarray) -> jnp.ndarray:
    """Project world points [..., 3] to NDC [..., 3] (for parity checks and
    the novel-view VDI renderer)."""
    p = jnp.concatenate([point_w, jnp.ones_like(point_w[..., :1])], axis=-1)
    clip = p @ (proj @ view).T
    return clip[..., :3] / clip[..., 3:4]


def orbit(cam: Camera, yaw: jnp.ndarray, pitch: jnp.ndarray = 0.0) -> Camera:
    """Rotate the eye around the target (≅ rotateCamera benchmark sweep,
    reference DistributedVolumes.kt:527-623)."""
    rel = cam.eye - cam.target
    cy, sy = jnp.cos(yaw), jnp.sin(yaw)
    rel = jnp.stack([cy * rel[0] + sy * rel[2], rel[1],
                     -sy * rel[0] + cy * rel[2]])
    cp, sp = jnp.cos(pitch), jnp.sin(pitch)
    rel = jnp.stack([rel[0], cp * rel[1] - sp * rel[2],
                     sp * rel[1] + cp * rel[2]])
    return cam._replace(eye=cam.target + rel)


def _normalize(v: jnp.ndarray, axis: int = -1, eps: float = 1e-12) -> jnp.ndarray:
    return v / jnp.maximum(jnp.linalg.norm(v, axis=axis, keepdims=True), eps)
