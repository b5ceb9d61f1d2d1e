"""Vortex-ring Navier-Stokes simulation (BASELINE.md Config 3; ≅ the
reference's vortex-in-cell OpenFPM demo, README.md:4-8 vortex_in_cell.gif,
whose vorticity-magnitude volume is rendered in-situ).

A stable-fluids incompressible solver on a periodic box, built from
TPU-friendly primitives only:

- semi-Lagrangian advection (trilinear back-trace; in XLA one gather per
  point of all eight corners of all three components, on a TPU and a
  mesh a windowed Pallas kernel with no gather at all),
- spectral diffusion + pressure projection in one rFFT round-trip
  (jnp.fft; exact div-free projection, unconditionally stable).

State is velocity ``u f32[3, D, H, W]``; the rendered field is |curl u|
(vorticity magnitude), normalized to ≈[0, 1].

A session advances it through `frame_program`: ONE jitted program per
frame — n steps, then the rendered field, and what each step's
back-trace read — whose in and out placements are fixed on a mesh (u
z-sharded ``P(None, axis, None, None)``, the field ``P(axis, None,
None)``), with the phases ``sim_advect``, ``sim_project`` and
``sim_field`` scoped inside it (obs/profiler.py). On a mesh a rank
back-traces only its own z-slab, and since PR 38 from a window bounded
by the step's displacement (`advect_window`): its slab plus
`window_halo` planes of each ring neighbour, taken by ``ppermute``. The
program decides on its input — the largest ``|dt u_z|`` over all ranks
against the halo, in a ``lax.cond`` — and holds the old path as the
other branch: the field all-gathered, turned into 24-wide cells and
gathered from (`whole_field`), which is also all that one device and a
slab too thin to window ever run. The windowed branch is the same cells
+ one-gather code on the smaller operand or, on a TPU where its tiles
fit, the kernel of `sim/pallas_backtrace.py`. The transforms (DFT
matmuls on a TPU) and the field's differences are laid over the ranks
by the partitioner (PERF.md §5 says what the compiled program and its
trace hold).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from scenery_insitu_tpu.obs.profiler import phase
from scenery_insitu_tpu.ops.pallas_util import should_interpret
from scenery_insitu_tpu.ops.sampling import sample_trilinear
from scenery_insitu_tpu.sim import pallas_backtrace


class VortexParams(NamedTuple):
    viscosity: jnp.ndarray   # kinematic viscosity
    dt: jnp.ndarray

    @classmethod
    def create(cls, viscosity=1e-3, dt=0.1):
        a = lambda x: jnp.asarray(x, jnp.float32)
        return cls(a(viscosity), a(dt))


class VortexFlow(NamedTuple):
    u: jnp.ndarray           # f32[3, D, H, W] velocity (x, y, z components)
    params: VortexParams

    @classmethod
    def init_ring(cls, grid: Tuple[int, int, int],
                  params: VortexParams = None, rings: int = 2,
                  radius: float = 0.22, strength: float = 6.0) -> "VortexFlow":
        """One or two coaxial vortex rings travelling along +z (two rings
        leapfrog — the classic demo): `ring_velocity`, made div-free."""
        flow = cls(ring_velocity(grid, rings, radius, strength),
                   params or VortexParams.create())
        return flow._replace(u=project_divfree(flow.u, flow.params, 0.0))

    @property
    def field(self) -> jnp.ndarray:
        """Normalized vorticity magnitude f32[D, H, W] for rendering."""
        return render_field(self.u)


def ring_velocity(grid: Tuple[int, int, int], rings: int = 2,
                  radius: float = 0.22,
                  strength: float = 6.0) -> jnp.ndarray:
    """The swirling velocity of `VortexFlow.init_ring`'s rings before its
    projection: f32[3, D, H, W] in voxel units / time."""
    d, h, w = grid
    z, y, x = jnp.meshgrid(
        (jnp.arange(d) + 0.5) / d - 0.5,
        (jnp.arange(h) + 0.5) / h - 0.5,
        (jnp.arange(w) + 0.5) / w - 0.5, indexing="ij")
    u = jnp.zeros((3, d, h, w), jnp.float32)
    offsets = [-0.12, 0.12][:rings] if rings > 1 else [0.0]
    for zo in offsets:
        # solid-core ring vorticity -> induced velocity via stream fn
        # approximation: add a swirling velocity field around the ring
        # core circle (x²+y² = radius², z = zo)
        rho = jnp.sqrt(x * x + y * y) + 1e-6
        # distance from the ring core
        dr = jnp.sqrt((rho - radius) ** 2 + (z - zo) ** 2)
        core = 0.05
        swirl = strength * jnp.exp(-(dr / core) ** 2 / 2)
        # toroidal vorticity direction: (-y/rho, x/rho, 0); velocity
        # circulates in the (rho, z) plane around the core:
        #   u_rho ∝ -(z - zo), u_z ∝ (rho - radius)
        u_rho = -swirl * (z - zo) / (dr + 1e-6) * core
        u_z = swirl * (rho - radius) / (dr + 1e-6) * core
        u = u.at[0].add(u_rho * x / rho)
        u = u.at[1].add(u_rho * y / rho)
        u = u.at[2].add(u_z)
    # velocity is kept in voxel units / time everywhere (advection
    # back-traces in voxel coords); the ring was built in domain units
    scale = jnp.array([w, h, d], jnp.float32).reshape(3, 1, 1, 1)
    return u * scale


def _grad_axes(shape):
    """Periodic spectral wavenumbers for (D, H, W) with Nyquist bins zeroed:
    the Nyquist derivative is sign-ambiguous and a nonzero choice breaks the
    Hermitian symmetry of the projected spectrum (irfft then silently drops
    the asymmetric part, leaving divergence behind)."""
    d, h, w = shape

    def axis_freqs(n, rfft=False):
        k = (jnp.fft.rfftfreq(n) if rfft else jnp.fft.fftfreq(n)) * 2 * jnp.pi
        if n % 2 == 0:
            k = k.at[-1 if rfft else n // 2].set(0.0)
        return k

    return jnp.meshgrid(axis_freqs(d), axis_freqs(h), axis_freqs(w, True),
                        indexing="ij")


def vorticity(u: jnp.ndarray) -> jnp.ndarray:
    """curl(u) via central differences on the periodic grid (grid units)."""
    def dd(f, axis):
        return 0.5 * (jnp.roll(f, -1, axis) - jnp.roll(f, 1, axis))
    ux, uy, uz = u[0], u[1], u[2]
    # axes of f[D, H, W]: 0=z, 1=y, 2=x
    wx = dd(uz, 1) - dd(uy, 0)
    wy = dd(ux, 0) - dd(uz, 2)
    wz = dd(uy, 2) - dd(ux, 1)
    return jnp.stack([wx, wy, wz])


def render_field(u: jnp.ndarray) -> jnp.ndarray:
    """|curl u| over its largest value: f32[D, H, W] in [0, 1)."""
    w = vorticity(u)
    mag = jnp.sqrt(jnp.sum(w * w, axis=0))
    return mag / (jnp.max(mag) + 1e-6)


# the eight corners of a trilinear cell, (dz, dy, dx), low corner first
_CORNERS = tuple((a, b, c) for a in (0, 1) for b in (0, 1) for c in (0, 1))

# the shallowest halo a rank's window takes: the bound leaves H - 1 voxels
# of room, and under two voxels of it nearly every flow would give way
_HALO_MIN = 4


def window_halo(planes: int, ranks: int) -> int:
    """H, the z-planes a rank takes from each ring neighbour to back-trace
    its own ``planes`` from a window of ``planes + 2 H``: a quarter of the
    slab, and 0 — no window, the whole field — where there is no
    neighbour or the slab is thinner than ``2 H``."""
    halo = max(planes // 4, _HALO_MIN)
    return halo if ranks > 1 and planes >= 2 * halo else 0


def _window_kernel(planes: int, halo: int, h: int, w: int) -> bool:
    """Does the windowed Pallas kernel take a rank's back-trace here? On
    a TPU, where its tiles fit the slab; elsewhere the window is read by
    the XLA gather."""
    return (jax.default_backend() == "tpu"
            and pallas_backtrace.fits(planes, halo, h, w))


def _back_trace(u, dt, first, d):
    """Where each point of ``u`` (planes ``first ...`` of ``d``) came
    from: per axis z, y, x the low corner's index in the grid padded by
    one wrap layer on BOTH faces, and the weight of the high corner."""
    _, planes, h, w = u.shape
    z, y, x = jnp.meshgrid(jnp.arange(planes, dtype=jnp.float32) + 0.5,
                           jnp.arange(h, dtype=jnp.float32) + 0.5,
                           jnp.arange(w, dtype=jnp.float32) + 0.5,
                           indexing="ij")
    z = z + jnp.asarray(first, jnp.float32)
    low, frac = [], []
    # velocity components are in grid-units / time; component 2 moves
    # along z, 1 along y, 0 along x
    for comp, at, n in ((2, z, d), (1, y, h), (0, x, w)):
        # the back-traced position in the padded grid, less the half cell
        # of the centres: positions in [0, 0.5) blend f[n-1] with f[0]
        # across the low boundary too
        p = jnp.mod(at - dt * u[comp], n) + 0.5
        i0 = jnp.floor(p)
        low.append(i0.astype(jnp.int32))
        frac.append(p - i0)
    return low, frac


def _gather_blend(padded, low, frac):
    """The trilinear blend at (``low``, ``frac``) of ``padded``
    f32[Z, Y, X, 3], in which every low corner and its seven neighbours
    towards +z, +y, +x lie: ONE gather per point, of a cell that holds
    all 24 values it blends side by side (built by rolls)."""
    (z0, y0, x0), (fz, fy, fx) = low, frac
    pz, py, px, _ = padded.shape
    cells = jnp.concatenate(
        [jnp.roll(padded, (-dz, -dy, -dx), (0, 1, 2))
         for dz, dy, dx in _CORNERS], -1).reshape(pz * py * px, 24)
    got = cells.at[(z0 * py + y0) * px + x0].get(mode="promise_in_bounds")
    weight = jnp.stack(
        [(fz if dz else 1.0 - fz) * (fy if dy else 1.0 - fy)
         * (fx if dx else 1.0 - fx) for dz, dy, dx in _CORNERS], -1)
    out = jnp.sum(got.reshape(got.shape[:-1] + (8, 3)) * weight[..., None],
                  axis=-2)
    return jnp.moveaxis(out, -1, 0)


def _wrap_pad(u, z: bool):
    """``u`` f32[3, Z, H, W] as f32[Z', H + 2, W + 2, 3] with one wrap
    layer on both faces of y and x, and of z where asked."""
    return jnp.pad(jnp.moveaxis(u, 0, -1),
                   ((int(z),) * 2, (1, 1), (1, 1), (0, 0)), mode="wrap")


def advect_semilagrangian(u: jnp.ndarray, dt: jnp.ndarray) -> jnp.ndarray:
    """Back-trace each grid point through the velocity field and resample
    trilinearly (periodic wrap), on one device: `advect_window`'s field
    alone."""
    return advect_window(u, dt)[0]


def advect_window(u: jnp.ndarray, dt: jnp.ndarray, axis=None,
                  ranks: int = 1):
    """The semi-Lagrangian step and what it read: ``(u, window)``, the
    advected field and f32[3] ``(reach, H, windowed)`` — the largest
    ``|dt u_z|`` of the step in voxels over all ranks, the halo of the
    window the program holds (0 where it holds none) and 1.0 where the
    step read it.

    A step reaches only as far as ``dt |u|``. Under ``shard_map`` over
    the mesh axis ``axis`` of ``ranks`` devices ``u`` is the rank's own
    z-slab, and the rank back-traces it from a WINDOW: its slab plus
    ``H = window_halo(...)`` planes of each ring neighbour (two
    ``ppermute``s; the periodic wrap in z is the ring). The window
    serves a step iff every low corner and the one above it lie inside
    it, which ``reach < H - 1`` ensures; the reach is a ``pmax``, the
    same on every rank, and ``lax.cond`` on it takes either the window
    or ``whole_field``: the rank all-gathers the field and back-traces
    its planes from all of it, as every step did until PR 38. Without an
    ``axis`` (one device: nobody to take a halo from) and on a slab
    thinner than ``2 H`` there is no window and no ``cond``.

    In XLA the resampling is ONE gather per point either way
    (`_gather_blend`). On a TPU a gather costs by the index far more
    than by the width of what it fetches (PERF.md §6, PR 37: 24 scalar
    gathers per point took 810 ms of a 256^3 step on four ranks), and
    building the cells costs by the planes they cover (§6, PR 38), which
    is what the window cuts; where `_window_kernel` says so the window is
    read by `pallas_backtrace.back_trace` instead, from the same low
    corners and weights, with neither cells nor gather."""
    _, planes, h, w = u.shape
    d, halo = planes * ranks, window_halo(planes, ranks)
    first = 0 if axis is None else jax.lax.axis_index(axis) * planes
    reach = jnp.max(jnp.abs(dt * u[2]))
    if axis is not None:
        reach = jax.lax.pmax(reach, axis)
    low, frac = _back_trace(u, dt, first, d)

    def whole_field(u):
        whole = (u if axis is None
                 else jax.lax.all_gather(u, axis, axis=1, tiled=True))
        return _gather_blend(_wrap_pad(whole, z=True), low, frac)

    def windowed(u):
        up = [(i, (i + 1) % ranks) for i in range(ranks)]
        down = [(j, i) for i, j in up]
        window = jnp.concatenate(
            [jax.lax.ppermute(u[:, -halo:], axis, up), u,
             jax.lax.ppermute(u[:, :halo], axis, down)], 1)
        # the padded grid's plane z0 is the grid's z0 - 1, and the
        # window's plane 0 the grid's first - H
        inside = [jnp.mod(low[0] - 1 - first + halo, d)] + low[1:]
        if _window_kernel(planes, halo, h, w):
            return pallas_backtrace.back_trace(
                window, inside, frac, halo=halo,
                interpret=should_interpret())
        return _gather_blend(_wrap_pad(window, z=False), inside, frac)

    if halo:
        fits = reach < halo - 1
        out = jax.lax.cond(fits, windowed, whole_field, u)
    else:
        fits, out = False, whole_field(u)
    return out, jnp.stack([reach, jnp.float32(halo),
                           jnp.asarray(fits, jnp.float32)])


def project_divfree(u: jnp.ndarray, params: VortexParams,
                    dt_override=None) -> jnp.ndarray:
    """Spectral viscous decay + exact Leray projection onto div-free fields."""
    dt = params.dt if dt_override is None else jnp.asarray(dt_override, jnp.float32)
    _, d, h, w = u.shape
    kz, ky, kx = _grad_axes((d, h, w))
    k2 = kx * kx + ky * ky + kz * kz
    uh = jnp.stack([jnp.fft.rfftn(u[i]) for i in range(3)])
    decay = jnp.exp(-params.viscosity * k2 * dt)
    uh = uh * decay
    # remove the component along k: uh -= k (k·uh)/k²
    kdotu = kx * uh[0] + ky * uh[1] + kz * uh[2]
    k2s = jnp.where(k2 == 0, 1.0, k2)
    uh = uh - jnp.stack([kx, ky, kz]) * (kdotu / k2s)
    return jnp.stack([jnp.fft.irfftn(uh[i], s=(d, h, w)) for i in range(3)]
                     ).astype(jnp.float32)


def seed_tracers(grid: Tuple[int, int, int], n: int,
                 seed: int = 0) -> jnp.ndarray:
    """f32[N, 3] tracer positions in voxel coordinates (x, y, z), seeded
    uniformly in the central half of the box (where the rings live)."""
    d, h, w = grid
    key = jax.random.PRNGKey(seed)
    u01 = jax.random.uniform(key, (n, 3))
    lo = jnp.array([w * 0.25, h * 0.25, d * 0.25], jnp.float32)
    ext = jnp.array([w * 0.5, h * 0.5, d * 0.5], jnp.float32)
    return lo + u01 * ext


def tracer_velocities(u: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    """Sample the flow velocity at tracer positions -> f32[N, 3] (vx,vy,vz
    in voxel units/time). Periodic wrap via the same pad trick as
    advect_semilagrangian; sample_trilinear expects [D, H, W] + (x,y,z)."""
    def samp(f):
        fp = jnp.pad(f, ((1, 1), (1, 1), (1, 1)), mode="wrap")
        return sample_trilinear(fp, pos + 1.0)

    return jnp.stack([samp(u[0]), samp(u[1]), samp(u[2])], axis=-1)


def advect_tracers(u: jnp.ndarray, pos: jnp.ndarray,
                   dt: jnp.ndarray) -> jnp.ndarray:
    """Advect passive tracers through the flow (BASELINE.md Config 5's
    500k-tracer hybrid). pos f32[N, 3] voxel coords (x, y, z); periodic
    wrap. One forward-Euler step per call — the flow field is smooth and
    the dt matches the solver's."""
    _, d, h, w = u.shape
    vel = tracer_velocities(u, pos)
    box = jnp.array([w, h, d], jnp.float32)
    return jnp.mod(pos + dt * vel, box)


def tracers_to_world(pos: jnp.ndarray, origin: jnp.ndarray,
                     spacing: jnp.ndarray) -> jnp.ndarray:
    """Voxel-coordinate tracers -> world positions (x, y, z)."""
    return origin + pos * spacing


def step(flow: VortexFlow) -> VortexFlow:
    u = advect_semilagrangian(flow.u, flow.params.dt)
    u = project_divfree(u, flow.params)
    return flow._replace(u=u)


@partial(jax.jit, static_argnums=1)
def multi_step(flow: VortexFlow, n: int) -> VortexFlow:
    return jax.lax.fori_loop(0, n, lambda _, f: step(f), flow)


def frame_program(mesh=None, axis=None):
    """The jitted program of one frame of a session's vortex sim,
    ``vortex_frame(u, params, n) -> (u, field, windows)``: ``n`` steps
    (static, and walked statically: a frame takes a few, and a loop's
    carry costs whole-field copies around it), then the rendered field;
    ``windows`` f32[n, 3] holds each step's ``(reach, H, windowed)``
    (`advect_window`), a few bytes that say which branch the input asked
    for.

    On a ``mesh`` the placements are fixed — u z-sharded over ``axis``
    going in and coming out, the parameters replicated, the field
    z-sharded as the render step takes it, ``windows`` replicated — so
    the second frame compiles nothing and no eager op has to place
    anything; a rank back-traces only its own z-slab, from its window or
    from the whole field (`advect_window` under ``shard_map``: the
    program holds both, and the halo ``ppermute``s or the all-gather run
    inside the branch taken), and the transforms and the field's
    differences are the partitioner's."""
    if mesh is None:
        advect, shardings = advect_window, {}
    else:
        from jax.sharding import NamedSharding, PartitionSpec as P

        slab = P(None, axis, None, None)
        advect = jax.shard_map(
            partial(advect_window, axis=axis, ranks=mesh.shape[axis]),
            mesh=mesh, in_specs=(slab, P()), out_specs=(slab, P()),
            # a Pallas call (and its interpreter) carries no varying-axes
            # types, as under `sim/pallas_stencil`'s shard_map
            check_vma=False)
        shardings = dict(
            in_shardings=(NamedSharding(mesh, slab),
                          NamedSharding(mesh, P())),
            out_shardings=(NamedSharding(mesh, slab),
                           NamedSharding(mesh, P(axis, None, None)),
                           NamedSharding(mesh, P())))

    def vortex_frame(u, params, n):
        windows = []
        for _ in range(n):
            with phase("sim_advect"):
                u, window = advect(u, params.dt)
            windows.append(window)
            with phase("sim_project"):
                u = project_divfree(u, params)
        with phase("sim_field"):
            field = render_field(u)
        return u, field, jnp.stack(windows) if n else jnp.zeros((0, 3))

    return jax.jit(vortex_frame, static_argnums=2, **shardings)
