"""Gray-Scott 3D reaction-diffusion — the reference's headline demo workload
(README.md:4-8 gray_scott.gif ran on OpenFPM across 8 nodes; here it is a
built-in JAX simulation so the framework runs standalone, which the
reference explicitly could not: README.md:16 "can not be used standalone").

The update is pure elementwise + 6-point Laplacian stencil (periodic BC via
jnp.roll), so under jit with a z-sharded state XLA lowers the rolls to
ppermute halo exchanges over ICI automatically — the same decomposition the
render pipeline uses. That formulation (`multi_step`) is the plain reference;
`multi_step_fast` runs the time-fused Pallas kernel where it can, on one
device and on a z-sharded state alike.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from scenery_insitu_tpu.config import SimConfig


class GrayScottParams(NamedTuple):
    f: jnp.ndarray      # feed rate
    k: jnp.ndarray      # kill rate
    du: jnp.ndarray     # diffusion of u
    dv: jnp.ndarray     # diffusion of v
    dt: jnp.ndarray

    @classmethod
    def create(cls, f=None, k=None, du=None, dv=None, dt=None):
        # defaults come from SimConfig — single source of truth
        d = SimConfig()
        a = lambda x, dflt: jnp.asarray(dflt if x is None else x, jnp.float32)
        return cls(a(f, d.gs_f), a(k, d.gs_k), a(du, d.gs_du),
                   a(dv, d.gs_dv), a(dt, d.dt))


class GrayScott(NamedTuple):
    u: jnp.ndarray      # f32[D, H, W]
    v: jnp.ndarray      # f32[D, H, W]
    params: GrayScottParams

    @classmethod
    def init(cls, grid: Tuple[int, int, int], params: GrayScottParams = None,
             seed: int = 0, n_seeds: int = 4, sharding=None) -> "GrayScott":
        """Uniform u=1, v=0 with one central seed cube (quarter-width — small
        seeds diffuse away in 3D) plus ``n_seeds`` random satellite cubes.

        u and v are born in ``sharding`` (default: whole, on the default
        device) by ONE jitted program, `_seed_cubes`: with a
        ``NamedSharding(mesh, P(axis, None, None))`` every rank writes its
        own z-slab and nothing else, so a grid no single device could
        hold (1024^3 on four chips: 2.15 GB of state a rank) has a start;
        the values are the same whatever the sharding."""
        d, h, w = grid
        rs = max(min(d, h, w) // 8, 2)
        hi = jnp.array([d - rs, h - rs, w - rs])
        centers = [jnp.array([d // 2, h // 2, w // 2])] + [
            jax.random.randint(k, (3,), rs, hi)
            for k in jax.random.split(jax.random.PRNGKey(seed), n_seeds)]
        radii = [max(min(d, h, w) // 4, 2)] + [rs] * n_seeds
        u, v = _seed_cubes(tuple(grid), sharding)(
            jnp.stack(centers).astype(jnp.int32),
            jnp.array(radii, jnp.int32))
        return cls(u, v, params or GrayScottParams.create())

    @classmethod
    def from_config(cls, cfg: SimConfig, seed: int = 0,
                    sharding=None) -> "GrayScott":
        return cls.init(tuple(cfg.grid),
                        GrayScottParams.create(cfg.gs_f, cfg.gs_k,
                                               cfg.gs_du, cfg.gs_dv, cfg.dt),
                        seed=seed, sharding=sharding)

    @property
    def field(self) -> jnp.ndarray:
        """The scalar field rendered in-situ (v concentration, ≈[0, 1])."""
        return self.v


@lru_cache(maxsize=8)       # a process builds a handful: bounded
def _seed_cubes(grid, sharding=None):
    """The program that builds the Gray-Scott start: ``(centers i32[n, 3],
    radii i32[n]) -> (u, v)`` f32[grid], u = 1 and v = 0, and u = 0.5,
    v = 0.25 inside any of the cubes |index - centers[i]| < radii[i]
    (traced, so one program serves every seed). Each cell is decided
    from its own index — three `iota`s that fuse into the selects, no
    index volume — and u and v leave in ``sharding`` (`out_shardings`),
    so the partitioner gives every device its own block of the iotas
    and of nothing larger."""
    def seed_cubes(centers, radii):
        zz, yy, xx = (jax.lax.broadcasted_iota(jnp.int32, grid, a)
                      for a in range(3))
        inside = jnp.zeros(grid, bool)
        for i in range(centers.shape[0]):
            c, r = centers[i], radii[i]
            inside |= ((jnp.abs(zz - c[0]) < r) & (jnp.abs(yy - c[1]) < r)
                       & (jnp.abs(xx - c[2]) < r))
        return (jnp.where(inside, jnp.float32(0.5), jnp.float32(1.0)),
                jnp.where(inside, jnp.float32(0.25), jnp.float32(0.0)))

    return jax.jit(seed_cubes, out_shardings=sharding)


def _laplacian(x: jnp.ndarray) -> jnp.ndarray:
    return (jnp.roll(x, 1, 0) + jnp.roll(x, -1, 0)
            + jnp.roll(x, 1, 1) + jnp.roll(x, -1, 1)
            + jnp.roll(x, 1, 2) + jnp.roll(x, -1, 2) - 6.0 * x)


def step(state: GrayScott) -> GrayScott:
    u, v, p = state.u, state.v, state.params
    uvv = u * v * v
    du = p.du * _laplacian(u) - uvv + p.f * (1.0 - u)
    dv = p.dv * _laplacian(v) + uvv - (p.f + p.k) * v
    return GrayScott(u + p.dt * du, v + p.dt * dv, p)


@partial(jax.jit, static_argnums=1)
def multi_step(state: GrayScott, n: int) -> GrayScott:
    return jax.lax.fori_loop(0, n, lambda _, s: step(s), state)


def _z_ring(x):
    """How ``x`` f32[D, H, W] is laid out, as the fused kernel has to
    know it: ``(mesh, axis, local shape)`` of a field z-sharded over more
    than one rank (``NamedSharding(mesh, P(axis, None, None))``, the
    placement of a session's state on a multi-rank mesh);
    ``(None, None, x.shape)`` of one that a single device holds or a
    tracer stands for; None of any other placement."""
    from jax.sharding import Mesh, NamedSharding

    sh = getattr(x, "sharding", None)
    if (isinstance(x, jax.core.Tracer) or sh is None
            or len(sh.device_set) == 1):
        return None, None, tuple(x.shape)
    if not (isinstance(sh, NamedSharding) and isinstance(sh.mesh, Mesh)):
        return None
    axis, *rest = tuple(sh.spec) + (None,) * (3 - len(sh.spec))
    if axis is None or any(a is not None for a in rest):
        return None
    return sh.mesh, axis, sh.shard_shape(x.shape)


def multi_step_fast(state: GrayScott, n: int) -> GrayScott:
    """The fast path: the fused Pallas stencil kernel on TPU
    (sim/pallas_stencil.py), giving way to `multi_step` — on the ledger —
    on other backends and on grids no tile of the kernel fits. A Mosaic
    refusal of the chosen tile is not caught: it reaches the caller.

    A state that is z-sharded over the ranks of a mesh runs the same
    kernels on every rank's shard, and each pass takes its outer z halo
    from the ring neighbours (`pallas_stencil.multi_step_pallas_sharded`);
    what decides then is whether a tile fits the SHARD. The kernel's own
    periodic wrap is per buffer, so a state placed in any other way over
    several devices gives way to `multi_step`, whose rolls XLA lowers to
    collectives whatever the placement."""
    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.sim import pallas_stencil as ps

    def roll(reason):
        # ledger only (warn=False): this runs per frame and the
        # downgrade is expected behavior of the platform or the grid —
        # but a run that was CONFIGURED fused and silently ran the roll
        # path must still end with that fact on the record (deduped,
        # counted)
        obs.degrade("sim.fused_stencil", "pallas", "xla_roll", reason,
                    warn=False)
        return multi_step(state, n)

    if jax.default_backend() != "tpu":
        return roll(f"backend is {jax.default_backend()!r}, not tpu")
    ring = _z_ring(state.u)
    if ring is None:
        return roll(f"sim state is placed as {state.u.sharding}: the fused "
                    "kernel serves one device or a z-sharded field")
    mesh, axis, shape = ring
    if not ps.fused_supported(shape, ring=mesh is not None):
        return roll(f"no fused-stencil tile fits grid {shape}"
                    + (f", one rank's shard of {tuple(state.u.shape)}"
                       if mesh is not None else "")
                    + " (needs W % 128 == 0, H % 8 == 0 and a tile under "
                      "the VMEM limit)")
    p = state.params
    if mesh is None:
        pvec = jnp.stack([p.f, p.k, p.du, p.dv, p.dt])
        u, v = ps.multi_step_pallas(state.u, state.v, pvec, n)
        return GrayScott(u, v, p)
    u, v = ps.multi_step_pallas_sharded(state.u, state.v, tuple(p), n, mesh,
                                        axis)
    rec = obs.get_recorder()
    if rec.enabled:
        exchanges, sent = ps.ring_halo_traffic(shape, n)
        rec.count("sim_halo_exchanges", exchanges)
        rec.count("sim_halo_bytes", sent)
    return GrayScott(u, v, p)


def multi_step_fast_ranges(state: GrayScott, n: int, bricks=None,
                           fused: bool = True):
    """`multi_step_fast` that ALSO returns per-brick min/max of the
    rendered field (ops/occupancy.FieldRanges) — the sim-fused update of
    the frame's occupancy pyramid. The fused Pallas path emits the
    ranges as a kernel epilogue (near-free: the slab is already in
    VMEM); every other path (off-TPU, a grid no tile fits, or
    ``fused=False`` pinning the XLA roll formulation) runs ONE lax
    reduction over the final field in data layout
    (`occupancy.field_ranges` — still cheaper than the legacy
    permute+reduce occupancy pass, and recorded on the fallback ledger
    unless the roll path was explicitly configured).

    ``bricks = (nzb, nyb)`` is the brick GRID (defaults to
    `occupancy.default_bricks`). Returns ``(state', FieldRanges)``."""
    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.ops import occupancy as occ
    from scenery_insitu_tpu.sim import pallas_stencil as ps

    nzb, nyb = bricks or occ.default_bricks(state.v.shape)
    if (fused and jax.default_backend() == "tpu"
            and ps.fused_supported(state.u.shape)):
        p = state.params
        pvec = jnp.stack([p.f, p.k, p.du, p.dv, p.dt])
        u, v, lo, hi = ps.multi_step_pallas_ranges(state.u, state.v,
                                                   pvec, n, nzb, nyb)
        return GrayScott(u, v, p), occ.FieldRanges(lo, hi)
    if fused:
        # configured fused but the epilogue cannot ride the kernel: the
        # advance itself still takes its own best path (multi_step_fast
        # ledgers its own degradations); only the ranges fall back here
        obs.degrade("occupancy.sim_ranges", "fused_epilogue",
                    "lax_reduce",
                    f"backend={jax.default_backend()!r}, grid="
                    f"{tuple(state.u.shape)}: no fused ranges schedule",
                    warn=False)
        st = multi_step_fast(state, n)
    else:
        st = multi_step(state, n)
    return st, occ.field_ranges(st.field, nzb, nyb)
