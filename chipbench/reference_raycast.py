"""The plain reference of one frame's IMAGE: a straightforward raycast of
a scalar volume on the slice march's intermediate grid, f32, plain
`jax.numpy`, no kernels, no matmul formulation. Imports nothing of the
program (and so nothing from `ops/slicer.py`): a later PR may change the
program, not the yardstick. (ROADMAP R-M10's independent raycaster; only
the dataset configuration compares with it so far.)

What it renders: for each pixel of the virtual camera's grid one ray from
the eye through the pixel's point on the reference plane; along it the
volume is sampled where the ray crosses each voxel PLANE of the march
axis (z here), bilinearly inside the plane; each sample goes through the
transfer function, its opacity is corrected for the length of the ray
between two planes, and the samples are accumulated front to back,
alpha-under, premultiplied.

The sampling rule is the one thing it SHARES with the program's march,
stated here so that it is a rule and not a coincidence: the samples lie
on the planes of the march axis (one per voxel plane, at the voxel
centres' z), not at equidistant steps along the ray; a plane closer to
the eye than `S_FLOOR` of the reference plane's distance is dropped; a
sample outside the volume's in-plane extent (half a voxel beyond the
outermost centres) is empty; inside it the outermost half voxel clamps
to the edge value. The grid is the march's own too (`axis_grid`, written
out again from its definition: the box's footprint seen from the eye on
the nearest slice plane, one voxel of margin, pixel centres), because
two images can only be compared pixel by pixel on one grid.

Every departure from the program: the interpolation is four gathers and
a weighted sum in f32 (the march does two banded matmuls per plane with
bfloat16 operands on a TPU); the volume is the widened f32 one (value /
255 for a u8 file, as `core/volume.load_raw` widens), where the program
may hold the file's integers and scale the interpolated value; no
occupancy skipping, no chunks, no supersegments: the image is the
direct accumulation, which a VDI decoded from its own view
(`reference.decode`) equals up to rounding, because a supersegment
stores the exact front-to-back composite of the samples it merged.

Only a march along z is defined (as `arith.intermediate_grid`): the x and
y regimes' grids wait for ROADMAP R-M8.
"""

import numpy as np

S_FLOOR = 1e-3          # min depth ratio: planes closer are dropped


def placement(dims_dhw) -> tuple:
    """(origin xyz, voxel size) of a volume centred on the world origin
    with its largest side spanning 2 world units: the session's rule."""
    d, h, w = dims_dhw
    vox = 2.0 / max(d, h, w)
    return np.array([-w * vox / 2, -h * vox / 2, -d * vox / 2]), vox


def axis_grid(eye, dims_dhw, ni: int, nj: int) -> dict:
    """The virtual camera of a march along z for this eye (float64): the
    eye looks along the axis toward the volume; the reference plane is the
    volume's nearest voxel plane; the frustum covers the box's eight
    corners projected from the eye onto that plane, plus one voxel of
    margin; pixel (j, i) is the centre of its cell, row 0 at the top."""
    eye = np.asarray(eye, np.float64)
    d, h, w = dims_dhw
    origin, vox = placement(dims_dhw)
    if abs(eye[2]) < max(abs(eye[0]), abs(eye[1])):
        raise ValueError("reference_raycast serves a march along z only")
    sign = -1.0 if eye[2] > 0 else 1.0      # toward the origin
    box = np.stack([origin, origin + np.array([w, h, d]) * vox])
    w0 = (box[0, 2] + 0.5 * vox) if sign > 0 else (box[1, 2] - 0.5 * vox)
    zp = max(sign * (w0 - eye[2]), vox)
    right_u = -sign                 # cross(forward, up)[x], forward = sign z
    xs, ys = [], []
    for bits in range(8):
        c = np.array([box[bits >> k & 1, k] for k in range(3)]) - eye
        ze = max(sign * c[2], zp)
        xs.append(right_u * c[0] * zp / ze)
        ys.append(c[1] * zp / ze)
    l, r = min(xs) - vox, max(xs) + vox
    b, t = min(ys) - vox, max(ys) + vox
    ndc_x = (np.arange(ni) + 0.5) / ni * 2 - 1
    ndc_y = 1.0 - (np.arange(nj) + 0.5) / nj * 2
    return {"eye": eye, "sign": sign, "zp": zp, "w0": w0, "vox": vox,
            "origin": origin,
            "u": eye[0] + (ndc_x * (r - l) + (r + l)) * 0.5 * right_u,
            "v": eye[1] + (ndc_y * (t - b) + (t + b)) * 0.5}


def _axis_samples(pos, origin, vox, n):
    """Voxel-centre bilinear support of world positions along one axis:
    (low index, high index, weight of high, valid)."""
    import jax.numpy as jnp

    x = (pos - origin) / vox - 0.5
    valid = (x >= -0.5) & (x <= n - 0.5)
    xc = jnp.clip(x, 0.0, n - 1.0)
    lo = jnp.clip(jnp.floor(xc), 0, n - 1).astype(jnp.int32)
    hi = jnp.minimum(lo + 1, n - 1)
    return lo, hi, xc - lo, valid


def render(volume, eye, ni: int, nj: int, alpha_points, dtype="float32"):
    """f32[4, nj, ni] premultiplied RGBA of `volume` (f32[D, H, W],
    normalised to [0, 1]) from `eye`, colours by the `grays` map (rgb =
    value) and opacity by the polyline `alpha_points` ((value, alpha)
    pairs, clamped outside). One loop over the planes, nearest first,
    with the whole grid's accumulator as its carry: 26 MB at 1280 x 1280,
    whatever the depth. `dtype` below float32 is the control: the same
    arithmetic with samples, weights and accumulator in that type."""
    import jax
    import jax.numpy as jnp

    d, h, w = volume.shape
    g = axis_grid(eye, (d, h, w), ni, nj)
    dt = jnp.dtype(dtype)
    f = lambda x: jnp.asarray(x, jnp.float32)
    eu, ev, ew = (f(x) for x in g["eye"])
    u, v, zp, vox = f(g["u"]), f(g["v"]), f(g["zp"]), f(g["vox"])
    ox, oy = f(g["origin"][0]), f(g["origin"][1])
    sign, w0 = f(g["sign"]), f(g["w0"])
    ax = f([p[0] for p in alpha_points])
    ay = f([p[1] for p in alpha_points])
    # the ray's length between two planes over the nominal step (a voxel)
    length = jnp.sqrt((v - ev)[:, None] ** 2 + (u - eu)[None, :] ** 2
                      + zp ** 2)
    ratio = (vox / zp) * length / vox

    def plane(vol, m, acc):
        wk = w0 + m * sign * vox
        s = sign * (wk - ew) / zp
        z = jnp.where(sign > 0, m, d - 1 - m)
        x0, x1, fx, okx = _axis_samples(eu + (u - eu) * s, ox, vox, w)
        y0, y1, fy, oky = _axis_samples(ev + (v - ev) * s, oy, vox, h)
        p = jax.lax.dynamic_index_in_dim(vol, z, 0, keepdims=False)
        p = p.astype(dt)
        rows0, rows1 = jnp.take(p, y0, axis=0), jnp.take(p, y1, axis=0)
        fx, fy = fx.astype(dt)[None, :], fy.astype(dt)[:, None]
        top = (jnp.take(rows0, x0, axis=1) * (1 - fx)
               + jnp.take(rows0, x1, axis=1) * fx)
        bot = (jnp.take(rows1, x0, axis=1) * (1 - fx)
               + jnp.take(rows1, x1, axis=1) * fx)
        val = jnp.clip(top * (1 - fy) + bot * fy, 0.0, 1.0)
        alpha = jnp.interp(val.astype(jnp.float32), ax, ay)
        live = oky[:, None] & okx[None, :] & (s > S_FLOOR)
        alpha = jnp.where(live, alpha, 0.0)
        alpha = 1.0 - jnp.power(jnp.clip(1.0 - alpha, 1e-7, 1.0), ratio)
        alpha = alpha.astype(dt)
        rgba = jnp.stack([val * alpha] * 3 + [alpha])
        return (acc + (1 - acc[3:4]) * rgba).astype(dt)

    with jax.default_matmul_precision("highest"):
        acc = jax.jit(lambda vol: jax.lax.fori_loop(
            0, d, lambda m, acc: plane(vol, m, acc),
            jnp.zeros((4, nj, ni), dt)))(volume)
    return np.asarray(acc.astype(jnp.float32))
