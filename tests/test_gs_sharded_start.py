"""The Gray-Scott start is born where the state lives (PR 41): one jitted
program with the state's sharding as its `out_shardings`
(`sim/grayscott._seed_cubes`), so that a grid no single device holds has
a start. The values are those of the eager whole-grid construction it
replaced — written out here in numpy, index volumes and all — bit for
bit, on one device and on a mesh, in `GrayScott.init` and in a session."""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from scenery_insitu_tpu.config import FrameworkConfig
from scenery_insitu_tpu.runtime.session import InSituSession
from scenery_insitu_tpu.sim import grayscott as gs


def eager_start(grid, seed=0, n_seeds=4):
    """`GrayScott.init` as it was: ones, zeros, three index volumes of the
    whole grid and one stamp per cube, the satellite cubes where
    `PRNGKey(seed)` puts them."""
    d, h, w = grid
    u, v = np.ones(grid, np.float32), np.zeros(grid, np.float32)
    zz, yy, xx = np.meshgrid(np.arange(d), np.arange(h), np.arange(w),
                             indexing="ij")

    def stamp(c, r):
        mask = ((np.abs(zz - c[0]) < r) & (np.abs(yy - c[1]) < r)
                & (np.abs(xx - c[2]) < r))
        u[mask], v[mask] = 0.5, 0.25

    stamp((d // 2, h // 2, w // 2), max(min(d, h, w) // 4, 2))
    rs = max(min(d, h, w) // 8, 2)
    for k in jax.random.split(jax.random.PRNGKey(seed), n_seeds):
        stamp(np.asarray(jax.random.randint(
            k, (3,), rs, np.array([d - rs, h - rs, w - rs]))), rs)
    return u, v


def sharding(devices):
    mesh = Mesh(np.array(jax.devices()[:devices]), ("ranks",))
    return NamedSharding(mesh, P("ranks", None, None))


def session(devices, grid):
    cfg = FrameworkConfig().with_overrides(
        f"sim.grid=[{','.join(map(str, grid))}]", "slicer.engine=mxu",
        "vdi.max_supersegments=4", "runtime.dataset=gray_scott",
        f"mesh.num_devices={devices}")
    return InSituSession(cfg).sim.state


CASES = {
    # how the start is made: (grid, seed, n_seeds, devices)
    "init-whole": lambda: (gs.GrayScott.init((32, 32, 32)),
                           ((32, 32, 32), 0, 4, 1)),
    "init-whole-seed3": lambda: (gs.GrayScott.init((16, 24, 32), seed=3,
                                                   n_seeds=2),
                                 ((16, 24, 32), 3, 2, 1)),
    "init-no-satellites": lambda: (gs.GrayScott.init((16, 16, 16),
                                                     n_seeds=0),
                                   ((16, 16, 16), 0, 0, 1)),
    "init-sharded-1": lambda: (gs.GrayScott.init((32, 32, 32),
                                                 sharding=sharding(1)),
                               ((32, 32, 32), 0, 4, 1)),
    "init-sharded-4": lambda: (gs.GrayScott.init((32, 32, 32),
                                                 sharding=sharding(4)),
                               ((32, 32, 32), 0, 4, 4)),
    "init-sharded-2-seed7": lambda: (gs.GrayScott.init(
        (8, 16, 128), seed=7, n_seeds=1, sharding=sharding(2)),
        ((8, 16, 128), 7, 1, 2)),
    "init-sharded-8-thin": lambda: (gs.GrayScott.init(
        (16, 8, 8), seed=1, sharding=sharding(8)), ((16, 8, 8), 1, 4, 8)),
    "session-1-device": lambda: (session(1, (32, 32, 32)),
                                 ((32, 32, 32), 0, 4, 1)),
    "session-4-devices": lambda: (session(4, (32, 32, 32)),
                                  ((32, 32, 32), 0, 4, 4)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_start_is_the_eager_one_bit_for_bit(case):
    state, (grid, seed, n_seeds, devices) = CASES[case]()
    want_u, want_v = eager_start(grid, seed, n_seeds)
    assert np.array_equal(np.asarray(state.u), want_u)
    assert np.array_equal(np.asarray(state.v), want_v)
    assert 0 < (want_v > 0).sum() < want_v.size
    for x in (state.u, state.v):
        assert x.dtype == np.float32
        assert len(x.sharding.device_set) == devices
        # every device holds its own z-slab and no more
        assert {s.data.shape for s in x.addressable_shards} == {
            (grid[0] // devices,) + tuple(grid[1:])}
    if devices > 1:
        assert state.u.sharding.is_equivalent_to(sharding(devices), 3)


def test_one_program_serves_every_seed():
    """The cubes' places are arguments, so a second seed compiles
    nothing."""
    gs.GrayScott.init((16, 16, 16), seed=1)
    build = gs._seed_cubes((16, 16, 16), None)
    before = build._cache_size()
    a = gs.GrayScott.init((16, 16, 16), seed=2)
    assert build._cache_size() == before
    assert not np.array_equal(np.asarray(a.v),
                              np.asarray(gs.GrayScott.init((16, 16, 16)).v))
