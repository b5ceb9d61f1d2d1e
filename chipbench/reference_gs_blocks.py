"""The plain Gray-Scott reference made and advanced in z-slabs, for a grid
whose whole start no single device holds (1024^3: u and v are 8.59 GB).
Imports nothing of the program: a later PR may change the program, not the
yardstick.

- `cubes`, `start_slab`: the start `reference.gray_scott_init` builds (one
  central cube of a quarter width, four satellite cubes of an eighth where
  PRNGKey(0) puts them), decided plane range by plane range from each
  cell's own index, so that any run of z-planes can be made alone, wrap
  included;
- `draw`: what `--seed` does to v, defined per CELL: v times
  (1 + amplitude * n), n in [-1, 1) a counter-based draw from
  (seed, z, y, x) — two rounds of a 32-bit integer mix over the cell's
  linear index, keyed by the seed's two 32-bit words — so a slab's
  perturbation needs nothing of any other slab (`reference.perturb`
  draws one stream for the whole grid);
- `field_after`: v after `steps` steps of the plain roll formulation
  (`reference.gray_scott_steps`'s arithmetic in its order), block by
  block: a block of planes is rolled together with `steps` start planes
  on each side, periodic in z, and the slab's own wrap spoils one plane
  an end per step, which are exactly the planes thrown away. Every cell's
  value goes through the operations the whole-grid roll gives it, so the
  two agree bit for bit (`chipbench/tests/test_gs_blocks.py`).
"""

import functools

import numpy as np

from chipbench import reference

MASK = 0xFFFFFFFF


def mix(h):
    """One round of a 32-bit integer mix (xor-shift, odd multiply, twice)
    of a uint32 array."""
    import jax.numpy as jnp

    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x846CA68B)
    return h ^ (h >> 16)


def mix_int(h: int) -> int:
    """`mix` of one Python int, in 32 bits."""
    h ^= h >> 16
    h = (h * 0x7FEB352D) & MASK
    h ^= h >> 15
    h = (h * 0x846CA68B) & MASK
    return h ^ (h >> 16)


def seed_keys(seed: int) -> tuple:
    """The two 32-bit keys of a seed of up to 64 bits: one from each of
    its words, so seeds past 2**32 differ too."""
    seed = int(seed)
    return (mix_int((seed & MASK) ^ 0x9E3779B9),
            mix_int(((seed >> 32) & MASK) ^ 0x85EBCA6B))


def draw(keys, z, y, x, grid):
    """n in [-1, 1) f32 of the cells (z, y, x) (uint32 arrays that
    broadcast; z already inside the grid): 24 bits of
    mix(mix(index ^ key0) ^ key1), index = (z * H + y) * W + x in 32 bits,
    as (bits * 2**-23) - 1, every step exact in f32."""
    import jax.numpy as jnp

    _, h, w = grid
    index = (z * jnp.uint32(h) + y) * jnp.uint32(w) + x
    bits = mix(mix(index ^ jnp.uint32(keys[0])) ^ jnp.uint32(keys[1]))
    return ((bits >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -23)
            - jnp.float32(1.0))


@functools.lru_cache(maxsize=None)
def cubes(grid) -> tuple:
    """((cz, cy, cx), r) of the five cubes, as Python ints: the central one
    and the four `reference.gray_scott_init` draws from PRNGKey(0)."""
    import jax
    import jax.numpy as jnp

    d, h, w = grid
    rs = max(min(d, h, w) // 8, 2)
    out = [((d // 2, h // 2, w // 2), max(min(d, h, w) // 4, 2))]
    for k in jax.random.split(jax.random.PRNGKey(0), 4):
        c = jax.random.randint(k, (3,), rs,
                               jnp.array([d - rs, h - rs, w - rs]))
        out.append((tuple(int(i) for i in c), rs))
    return tuple(out)


@functools.lru_cache(maxsize=None)
def _start_program(grid, planes: int):
    import jax
    import jax.numpy as jnp

    d, h, w = grid
    shape = (planes, h, w)
    places = cubes(grid)        # concrete, before anything is traced

    def start(z0, keys, amplitude):
        # plane i of the slab is plane (z0 + i) mod D of the grid
        zz = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) + z0) % d
        yy = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        xx = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
        u = jnp.ones(shape, jnp.float32)
        v = jnp.zeros(shape, jnp.float32)
        for c, r in places:
            mask = ((jnp.abs(zz - c[0]) < r) & (jnp.abs(yy - c[1]) < r)
                    & (jnp.abs(xx - c[2]) < r))
            u, v = jnp.where(mask, 0.5, u), jnp.where(mask, 0.25, v)
        n = draw(keys, *(i.astype(jnp.uint32) for i in (zz, yy, xx)), grid)
        return u, v * (1.0 + amplitude * n)

    return jax.jit(start)


def start_slab(grid, z0: int, planes: int, seed: int = 0,
               amplitude: float = 0.0) -> tuple:
    """(u, v) f32[planes, H, W] of the seeded start's planes z0 ..
    z0 + planes - 1, taken modulo D (so a slab may begin before plane 0
    and end past the last), on the device. Amplitude 0 is the unperturbed
    start, whatever the seed."""
    grid = tuple(int(n) for n in grid)
    keys = np.asarray(seed_keys(seed), np.uint32)
    return _start_program(grid, int(planes))(
        np.int32(int(z0) % grid[0]), keys, np.float32(amplitude))


@functools.lru_cache(maxsize=None)
def _roll_program(steps: int, dtype: str):
    """`reference.gray_scott_steps`'s loop, written out again so that one
    compiled program serves every slab of a shape (the original jits a
    new closure per call)."""
    import jax
    import jax.numpy as jnp

    p = reference.GS_DEFAULTS
    dt = jnp.dtype(dtype)

    def lap(x):
        return (jnp.roll(x, 1, 0) + jnp.roll(x, -1, 0) + jnp.roll(x, 1, 1)
                + jnp.roll(x, -1, 1) + jnp.roll(x, 1, 2)
                + jnp.roll(x, -1, 2) - 6.0 * x)

    def step(_, uv):
        u, v = uv
        uvv = u * v * v
        du = p["gs_du"] * lap(u) - uvv + p["gs_f"] * (1.0 - u)
        dv = p["gs_dv"] * lap(v) + uvv - (p["gs_f"] + p["gs_k"]) * v
        return ((u + p["dt"] * du).astype(dt), (v + p["dt"] * dv).astype(dt))

    def run(u, v):
        return jax.lax.fori_loop(0, steps, step,
                                 (u.astype(dt), v.astype(dt)))

    return jax.jit(run)


def blocks(depth: int, slab: int):
    """(z0, planes) of the blocks of at most `slab` planes that tile
    0 .. depth - 1; the last is the shorter one where `slab` does not
    divide `depth`."""
    return [(z0, min(slab, depth - z0)) for z0 in range(0, depth, slab)]


def start(grid, seed: int = 0, amplitude: float = 0.0,
          slab: int = 64) -> tuple:
    """The whole seeded start, assembled on the host from slabs."""
    grid = tuple(int(n) for n in grid)
    u, v = (np.empty(grid, np.float32) for _ in range(2))
    for z0, n in blocks(grid[0], slab):
        su, sv = start_slab(grid, z0, n, seed, amplitude)
        u[z0:z0 + n], v[z0:z0 + n] = np.asarray(su), np.asarray(sv)
    return u, v


def rolled_blocks(grid, seed: int, amplitude: float, steps: int,
                  slab: int = 64, dtype: str = "float32"):
    """(z0, planes, u, v) of every block after `steps` steps of the plain
    roll from the seeded start, held in `dtype`, on the device: each
    block is rolled with `steps` halo planes a side from the periodic
    start, and the halo, spoilt by the slab's own wrap, is cut off."""
    grid = tuple(int(n) for n in grid)
    roll = _roll_program(int(steps), dtype)
    for z0, n in blocks(grid[0], slab):
        u, v = roll(*start_slab(grid, z0 - steps, n + 2 * steps, seed,
                                amplitude))
        yield z0, n, u[steps:steps + n], v[steps:steps + n]


def _on_host(grid, rolled, which) -> list:
    """The fields `which` (0 = u, 1 = v) of `rolled_blocks`' blocks, each
    assembled into one f32 array on the host."""
    out = [np.empty(tuple(grid), np.float32) for _ in which]
    for z0, n, *uv in rolled:
        for whole, i in zip(out, which):
            whole[z0:z0 + n] = np.asarray(uv[i], np.float32)
    return out


def state_after(grid, seed: int, amplitude: float, steps: int,
                slab: int = 64, dtype: str = "float32") -> tuple:
    """(u, v) after `steps` steps, f32 on the host."""
    return tuple(_on_host(grid, rolled_blocks(
        grid, seed, amplitude, steps, slab, dtype), (0, 1)))


def field_after(grid, seed: int, amplitude: float, steps: int,
                slab: int = 64, dtype: str = "float32") -> np.ndarray:
    """The rendered field (v) after one frame's `steps` steps, f32 on the
    host (u is not copied: 4.3 GB at 1024^3)."""
    return _on_host(grid, rolled_blocks(
        grid, seed, amplitude, steps, slab, dtype), (1,))[0]
