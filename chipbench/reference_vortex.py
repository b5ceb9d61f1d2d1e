"""The plain reference of the vortex-ring configuration: the start, one
step and the rendered field, written out from the equations that
`scenery_insitu_tpu/sim/vortex.py` states in its docstrings. Imports
nothing of the program (neither `sim/vortex.py` nor `ops/sampling.py`): a
later PR may change the program, not the yardstick.

The equations, on a periodic box of D x H x W cells with the velocity
u = (u_x, u_y, u_z) in voxels per unit time, f[z, y, x]:

- start: two coaxial rings (radius 0.22, strength 6, core 0.05, at
  z = -0.12 and +0.12 of a box [-0.5, 0.5)^3 with cell centres at
  (i + 0.5) / n - 0.5), each a swirl around its core circle; scaled to
  voxel units; times (1 + amplitude * uniform(-1, 1)) drawn from
  `--seed`; made divergence-free (the projection below with dt = 0);
- step: semi-Lagrangian advection, u(p) <- u(p - dt u(p)) by trilinear
  interpolation between cell centres with periodic wrap; then spectral
  viscous decay exp(-nu k^2 dt) and the exact Leray projection
  u^ <- u^ - k (k . u^) / k^2, with the Nyquist bins of k zeroed;
- rendered field: |curl u| by central differences on the periodic grid,
  over (its largest value + 1e-6).

Departures from the program, each on purpose:

- arithmetic in float64 on the host (the program: float32 on the
  device); the state is HELD in `dtype` between steps as the program
  holds it in float32, and `dtype="bfloat16"` is the control;
- the transforms are `numpy.fft` (pocketfft) where a TPU runs DFT
  matmuls, so `jax.default_matmul_precision` has nothing to set here;
- the back-trace gathers its eight corners by explicit modular index
  arithmetic, not through a wrap-padded copy and a clamped sampler;
- only the noise comes from JAX (`jax.random.uniform` under
  `reference.seed_key(seed)`): the same numbers the session's start is
  perturbed with, whatever their placement.
"""

import numpy as np

RINGS = {"offsets": (-0.12, 0.12), "radius": 0.22, "strength": 6.0,
         "core": 0.05}
VISCOSITY, DT = 1e-3, 0.1


def hold(u: np.ndarray, dtype: str) -> np.ndarray:
    """f64 values as a state of `dtype` holds them."""
    if dtype == "float32":
        return u.astype(np.float32).astype(np.float64)
    import ml_dtypes

    return u.astype(getattr(ml_dtypes, dtype)).astype(np.float64)


def ring_velocity(grid) -> np.ndarray:
    """f64[3, D, H, W]: the two rings' swirl, in voxels per unit time."""
    d, h, w = grid
    z, y, x = np.meshgrid((np.arange(d) + 0.5) / d - 0.5,
                          (np.arange(h) + 0.5) / h - 0.5,
                          (np.arange(w) + 0.5) / w - 0.5, indexing="ij")
    r, s, core = RINGS["radius"], RINGS["strength"], RINGS["core"]
    rho = np.sqrt(x * x + y * y) + 1e-6
    u = np.zeros((3, d, h, w))
    for zo in RINGS["offsets"]:
        dr = np.sqrt((rho - r) ** 2 + (z - zo) ** 2)
        swirl = s * np.exp(-(dr / core) ** 2 / 2)
        u_rho = -swirl * (z - zo) / (dr + 1e-6) * core
        u[0] += u_rho * x / rho
        u[1] += u_rho * y / rho
        u[2] += swirl * (rho - r) / (dr + 1e-6) * core
    return u * np.array([w, h, d], np.float64).reshape(3, 1, 1, 1)


def noise(grid, seed: int) -> np.ndarray:
    """uniform(-1, 1) f32[3, D, H, W] from `--seed`."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference

    return np.asarray(jax.jit(lambda key: jax.random.uniform(
        key, (3, *grid), jnp.float32, -1.0, 1.0))(reference.seed_key(seed)))


def wavenumbers(grid) -> tuple:
    """(kz, ky, kx) broadcastable to the rfftn spectrum, Nyquist bins 0."""
    def freqs(n, real=False):
        k = (np.fft.rfftfreq(n) if real else np.fft.fftfreq(n)) * 2 * np.pi
        if n % 2 == 0:
            k[n // 2] = 0.0
        return k

    d, h, w = grid
    return (freqs(d)[:, None, None], freqs(h)[None, :, None],
            freqs(w, True)[None, None, :])


def project(u: np.ndarray, nu: float, dt: float) -> np.ndarray:
    """Viscous decay over `dt`, then the Leray projection."""
    grid = u.shape[1:]
    kz, ky, kx = wavenumbers(grid)
    k2 = kx * kx + ky * ky + kz * kz
    uh = [np.fft.rfftn(c) * np.exp(-nu * k2 * dt) for c in u]
    kdotu = (kx * uh[0] + ky * uh[1] + kz * uh[2]) / np.where(k2 == 0, 1.0,
                                                              k2)
    return np.stack([np.fft.irfftn(c - k * kdotu, s=grid, axes=(0, 1, 2))
                     for c, k in zip(uh, (kx, ky, kz))])


def advect(u: np.ndarray, dt: float) -> np.ndarray:
    """u at the back-traced positions, trilinear, periodic."""
    d, h, w = u.shape[1:]
    idx = np.meshgrid(np.arange(d), np.arange(h), np.arange(w),
                      indexing="ij")
    corners = []    # per axis z, y, x: (flat offset, weight) of the low
    #                 and of the high corner
    # component 2 moves along z (axis 0), 1 along y, 0 along x (axis 2)
    for comp, axis, n, stride in ((2, 0, d, h * w), (1, 1, h, w),
                                  (0, 2, w, 1)):
        p = np.mod(idx[axis] + 0.5 - dt * u[comp], n) - 0.5
        i0 = np.floor(p)
        frac = p - i0
        i0 = i0.astype(np.int64)
        corners.append(((np.mod(i0, n) * stride, 1.0 - frac),
                        (np.mod(i0 + 1, n) * stride, frac)))
    flat = u.reshape(3, -1)
    out = np.zeros_like(u)
    for iz, wz in corners[0]:
        for iy, wy in corners[1]:
            for ix, wx in corners[2]:
                at, weight = iz + iy + ix, wz * wy * wx
                for c in range(3):
                    out[c] += weight * np.take(flat[c], at)
    return out


def render_field(u: np.ndarray) -> np.ndarray:
    """|curl u| over its largest value (+ 1e-6)."""
    dd = lambda f, axis: 0.5 * (np.roll(f, -1, axis) - np.roll(f, 1, axis))
    ux, uy, uz = u
    mag = np.sqrt((dd(uz, 1) - dd(uy, 0)) ** 2 + (dd(ux, 0) - dd(uz, 2)) ** 2
                  + (dd(uy, 2) - dd(ux, 1)) ** 2)
    return mag / (mag.max() + 1e-6)


def start(grid, seed: int, amplitude: float, nu: float = VISCOSITY,
          dtype: str = "float32") -> np.ndarray:
    """The state frame 0 starts from, as `dtype` holds it (f64 values)."""
    u = ring_velocity(grid) * (1.0 + amplitude * noise(grid, seed))
    return hold(project(u, nu, 0.0), dtype)


def steps(u: np.ndarray, n: int, dt: float = DT, nu: float = VISCOSITY,
          dtype: str = "float32") -> np.ndarray:
    for _ in range(n):
        u = hold(project(advect(u, dt), nu, dt), dtype)
    return u


def frame0(grid, seed: int, amplitude: float, n: int, dt: float = DT,
           nu: float = VISCOSITY, dtype: str = "float32") -> np.ndarray:
    """The rendered field after frame 0's `n` steps from the seeded start,
    f32."""
    u0 = start(tuple(grid), seed, amplitude, nu, dtype)
    return render_field(steps(u0, n, dt, nu, dtype)).astype(np.float32)
