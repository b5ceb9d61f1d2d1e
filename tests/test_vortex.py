"""The vortex-ring solver as a session's per-frame program (PR 37):
`sim/vortex.frame_program` against the benchmark's plain reference
(`chipbench/reference_vortex.py`, float64 on the host, written from the
equations and independent of the program), on one device and z-sharded
over four; and through `InSituSession` on the four-rank mesh: placements
kept, nothing compiled after the first frame, the sim program's scope
table on the recorder, and no eager op between the program and the state.

Tolerances. The program is f32: its transforms, its back-traced
positions (up to 40 voxels, so 4e-6 of a voxel in the interpolation
weights) and its central differences each round at 1.2e-7 relative. Over
one step of a field whose speeds reach ~10 voxels per unit time that reads
4e-6 to 9e-6 in u and 7e-7 to 1e-6 in the rendered field (in [0, 1]) at
these sizes; the limits are five times that. The same step with its state
held in bfloat16 reads 2e-3 to 3e-3 in the field, hundreds of limits."""

import contextlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import harness, reference, reference_vortex
from scenery_insitu_tpu.parallel.mesh import make_mesh
from scenery_insitu_tpu.sim import vortex as vx

GRIDS = [(32, 32, 32), (24, 32, 40)]
SEEDS = [7, 2_147_483_659]
AMPLITUDE = 1e-3
U_ATOL, FIELD_ATOL = 5e-5, 5e-6


def seeded_start(grid, seed):
    """The seeded start as the benchmark's field source makes it, by the
    program's own functions: rings, perturbation, projection."""
    source = harness.load_file("source", os.path.join(
        harness.HERE, "sources", "sim_vortex.py"))
    params = vx.VortexParams.create()
    return source.seeded_start(grid, params, reference.seed_key(seed),
                               jnp.float32(AMPLITUDE)), params


def sharded(mesh):
    axis = mesh.axis_names[0]
    return (NamedSharding(mesh, P(None, axis, None, None)),
            NamedSharding(mesh, P(axis, None, None)))


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("grid", GRIDS, ids=["cube32", "box24x32x40"])
def test_step_and_field_against_the_plain_reference(grid, seed):
    u0, params = seeded_start(grid, seed)
    want_u0 = reference_vortex.start(grid, seed, AMPLITUDE)
    assert np.abs(np.asarray(u0) - want_u0).max() <= U_ATOL
    u1, field, _ = vx.frame_program()(u0, params, 1)
    want_u1 = reference_vortex.steps(want_u0, 1)
    assert np.abs(np.asarray(u1) - want_u1).max() <= U_ATOL
    assert np.abs(want_u1 - want_u0).max() > 1e-2       # it moved
    want = reference_vortex.render_field(want_u1)
    assert field.dtype == jnp.float32 and field.shape == grid
    assert np.abs(np.asarray(field) - want).max() <= FIELD_ATOL


@pytest.mark.parametrize("grid", GRIDS, ids=["cube32", "box24x32x40"])
def test_a_step_held_in_bfloat16_fails_the_tolerance(grid):
    """The control: the same mathematics with the state rounded to
    bfloat16 after the start and after the step is NOT inside the limit
    the f32 program meets."""
    u0, params = seeded_start(grid, SEEDS[0])
    _, field, _ = vx.frame_program()(u0, params, 1)
    low = reference_vortex.frame0(grid, SEEDS[0], AMPLITUDE, 1,
                                  dtype="bfloat16")
    assert np.abs(np.asarray(field) - low).max() > 100 * FIELD_ATOL
    hold = lambda u: u.astype(jnp.bfloat16).astype(jnp.float32)
    _, own, _ = vx.frame_program()(hold(u0), params, 1)
    assert np.abs(np.asarray(own) - np.asarray(field)).max() \
        > 100 * FIELD_ATOL


@pytest.mark.parametrize("steps", [0, 1, 2])
@pytest.mark.parametrize("grid", GRIDS, ids=["cube32", "box24x32x40"])
def test_the_sharded_program_equals_the_one_device_one(grid, steps):
    """What ties the ranks' shares to the whole: u z-sharded over four
    devices going in, (u, field) z-sharded coming out, equal to the
    one-device program's within the f32 rounding of a transform that is
    summed in another order."""
    mesh = make_mesh(4)
    u_sh, f_sh = sharded(mesh)
    u0, params = seeded_start(grid, SEEDS[1])
    one_u, one_f, one_w = vx.frame_program()(u0, params, steps)
    u, field, windows = vx.frame_program(mesh, mesh.axis_names[0])(
        jax.device_put(u0, u_sh), params, steps)
    # what each step read: the same reach on one device and on the mesh;
    # the 8-plane slabs hold a window (H = 4) and these flows stay in
    # it, the 6-plane slabs are thinner than 2 H and hold none
    halo = vx.window_halo(grid[0] // 4, 4)
    assert halo == (4 if grid[0] == 32 else 0)
    assert windows.shape == one_w.shape == (steps, 3)
    assert windows.sharding.is_fully_replicated
    np.testing.assert_array_equal(np.asarray(windows)[:, 0],
                                  np.asarray(one_w)[:, 0])
    assert (np.asarray(windows)[:, 1:] == [halo, bool(halo)]).all()
    assert (np.asarray(one_w)[:, 1:] == 0).all()
    assert u.sharding.is_equivalent_to(u_sh, 4)
    assert field.sharding.is_equivalent_to(f_sh, 3)
    assert len(u.sharding.device_set) == 4
    assert np.abs(np.asarray(u) - np.asarray(one_u)).max() <= U_ATOL
    assert np.abs(np.asarray(field) - np.asarray(one_f)).max() <= FIELD_ATOL
    if steps:
        want = reference_vortex.render_field(reference_vortex.steps(
            reference_vortex.start(grid, SEEDS[1], AMPLITUDE), steps))
        assert np.abs(np.asarray(field) - want).max() <= steps * FIELD_ATOL


@pytest.mark.parametrize("grid", GRIDS, ids=["cube32", "box24x32x40"])
def test_divergence_after_a_step(grid):
    """div u (spectral, the solver's Nyquist-zeroed derivative) under 1e-4
    of the largest speed after a step of the program."""
    u0, params = seeded_start(grid, SEEDS[0])
    u1, _, _ = vx.frame_program()(u0, params, 1)
    kz, ky, kx = reference_vortex.wavenumbers(grid)
    uh = [np.fft.rfftn(np.asarray(c, np.float64)) for c in u1]
    div = np.fft.irfftn(1j * (kx * uh[0] + ky * uh[1] + kz * uh[2]),
                        s=grid, axes=(0, 1, 2))
    assert np.abs(div).max() < 1e-4
    assert np.abs(div).max() < 1e-4 * float(jnp.abs(u1).max())


def test_the_frame_program_is_the_steps_and_the_field_it_replaced():
    """`vortex_frame` is `step` n times and `VortexFlow.field`, which the
    hybrid adapter and `models/pipelines.py` go on reading."""
    flow = vx.VortexFlow.init_ring((16, 16, 16))
    u, field, _ = vx.frame_program()(flow.u, flow.params, 2)
    two = vx.multi_step(flow, 2)
    np.testing.assert_allclose(np.asarray(u), np.asarray(two.u), atol=1e-5)
    np.testing.assert_allclose(np.asarray(field), np.asarray(two.field),
                               atol=1e-6)


# ------------------------------------------------------------ the window
#
# PR 38: on a mesh a rank back-traces its slab from a window (the slab +
# H halo planes of each ring neighbour) where the step's reach allows,
# and from the all-gathered field where it does not. 64 planes over four
# ranks are 16 a rank (H = 4, so a reach under 3 voxels windows) and over
# eight 8 a rank (H = 4 again); the seeded start there reaches 1.94 voxels
# on its first step, its rings' cores at planes 24.3 and 39.7, radius 7
# rows of 32 and 8.8 columns of 40.

DEEP = (64, 32, 40)
ROLLS = {"as_seeded": (0, 0, 0),
         "core_on_a_rank_boundary": (8, 0, 0),       # plane 32.3
         "core_on_the_z_wrap": (-24, 0, 0),          # planes 0.3 and 15.7
         "core_on_the_y_wrap": (0, 9, 0),            # row 16 + 7 + 9 = 32
         "core_on_the_x_wrap": (0, 0, 11)}           # column 20 + 8.8 + 11


def deep_start(roll=(0, 0, 0), scale=1.0):
    u0, params = seeded_start(DEEP, SEEDS[0])
    return jnp.roll(u0, roll, (1, 2, 3)) * scale, params


def advect_on(mesh):
    """`advect_window` alone, under `shard_map` as `frame_program` lays
    it over the mesh."""
    from functools import partial

    axis = mesh.axis_names[0]
    slab = P(None, axis, None, None)
    return jax.jit(jax.shard_map(
        partial(vx.advect_window, axis=axis, ranks=mesh.shape[axis]),
        mesh=mesh, in_specs=(slab, P()), out_specs=(slab, P()),
        check_vma=False))


@pytest.mark.parametrize("ranks", [4, 8])
@pytest.mark.parametrize("roll", ROLLS.values(), ids=ROLLS.keys())
def test_the_window_equals_the_whole_field_and_one_device(
        roll, ranks, monkeypatch):
    """Under the bound the windowed branch, the whole-field program (the
    same code with no window to hold: every step until PR 38) and the
    one-device back-trace give the same bits, wherever the cores lie."""
    mesh = make_mesh(ranks)
    u0, params = deep_start(roll)
    u_sh, _ = sharded(mesh)
    one, one_w = jax.jit(vx.advect_window)(u0, params.dt)
    got, window = advect_on(mesh)(jax.device_put(u0, u_sh), params.dt)
    assert np.asarray(window).tolist() == [float(one_w[0]), 4.0, 1.0]
    assert 1.5 < float(one_w[0]) < 3.0
    monkeypatch.setattr(vx, "window_halo", lambda planes, ranks: 0)
    whole, nowin = advect_on(mesh)(jax.device_put(u0, u_sh), params.dt)
    assert np.asarray(nowin).tolist() == [float(one_w[0]), 0.0, 0.0]
    assert np.abs(np.asarray(got) - np.asarray(u0)).max() > 1.0  # it moved
    np.testing.assert_array_equal(np.asarray(got), np.asarray(whole))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(one))


def vortex_adapter(mesh, dt, recorded=True):
    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.session import VolumeSimAdapter

    cfg = FrameworkConfig().with_overrides(
        "sim.kind=vortex", f"sim.grid={list(DEEP)}", f"sim.dt={dt}")
    obs.clear_ledger()
    return VolumeSimAdapter(cfg, mesh=mesh, obs=obs.Recorder(recorded))


def window_rows():
    from scenery_insitu_tpu import obs

    return [r for r in obs.ledger() if r["component"] == "sim.vortex_window"]


@pytest.mark.parametrize("scale,dt", [(2.5, 0.1), (1.0, 0.5), (1.0, 0.1)],
                         ids=["fast_start", "long_step", "as_seeded"])
def test_a_reach_beyond_the_window_takes_the_whole_field(scale, dt):
    """A start scaled (or a step lengthened) until `|dt u_z| > H - 1`
    takes `whole_field` inside the same program, still equals the
    one-device program, and each such step mints one `sim.vortex_window`
    row and is counted; the flow decays, so later steps window again.
    As seeded no step gives way and the ledger stays empty."""
    mesh = make_mesh(4)
    sim = vortex_adapter(mesh, dt)
    u0, _ = deep_start(scale=scale)
    sim.state = sim.state._replace(u=jax.device_put(u0, sharded(mesh)[0]))
    one, params, read, advance = u0, sim.state.params, [], sim._advance

    def keep(state, n):
        read.append(advance(state, n))
        return read[-1]

    sim._advance = keep
    with pytest.warns(UserWarning, match="sim.vortex_window") \
            if scale * dt > 0.1 else contextlib.nullcontext():
        for _ in range(3):
            sim.advance(2)
            one, field, _ = vx.frame_program()(one, params, 2)
            np.testing.assert_allclose(np.asarray(sim.state.u),
                                       np.asarray(one), atol=4 * U_ATOL)
            np.testing.assert_allclose(np.asarray(sim.field),
                                       np.asarray(field), atol=FIELD_ATOL)
        sim.close()
    assert not sim._windows
    steps = np.concatenate([np.asarray(out[2]) for out in read])
    rows = window_rows()
    gave_way = sum(r["count"] for r in rows)
    counters = sim._rec.counters
    assert counters["sim.vortex_window.whole_field"] == gave_way
    assert counters["sim.vortex_window.windowed"] == 6 - gave_way
    if scale * dt > 0.1:
        assert len(rows) == gave_way >= 1 and gave_way < 6
        assert all(r["from"] == "windowed" and r["to"] == "whole_field"
                   and "halo of 4 planes" in r["reason"] for r in rows)
    else:
        assert rows == [] and gave_way == 0
    # the program's own account agrees, step by step
    assert ((steps[:, 0] < 3) == (steps[:, 2] == 1)).all()
    assert int((steps[:, 2] == 0).sum()) == gave_way


class Late:
    """A frame's `windows` as the adapter sees it before the device has
    written it."""

    def __init__(self, steps):
        self.steps, self.ready = np.asarray(steps, np.float32), False

    def is_ready(self):
        return self.ready

    def __array__(self, dtype=None, copy=None):
        return self.steps


@pytest.mark.parametrize("end", ["poll", "close"])
def test_the_adapter_reads_only_what_the_device_has_written(end):
    """`poll` never waits: a frame whose `windows` is not there stays
    queued (and everything behind it, in order) until it is, or until
    `close`, which waits for all."""
    sim = vortex_adapter(make_mesh(4), 0.1)
    first, second = Late([[9.5, 4, 0], [2.0, 4, 1]]), Late([[1.0, 4, 1]])
    sim._windows.extend([first, second])
    second.ready = True
    sim.poll()
    assert len(sim._windows) == 2 and window_rows() == []
    assert "sim.vortex_window.windowed" not in sim._rec.counters
    with pytest.warns(UserWarning, match="reaches 9.5000 voxels"):
        if end == "poll":
            first.ready = True
            sim.poll()
        else:
            sim.close()
    assert not sim._windows and len(window_rows()) == 1
    assert sim._rec.counters["sim.vortex_window.windowed"] == 2
    assert sim._rec.counters["sim.vortex_window.whole_field"] == 1


@pytest.mark.parametrize("grid,ranks,holds", [
    ((32, 32, 32), 4, True), ((24, 32, 40), 4, False),
    ((32, 32, 32), 8, False), (DEEP, 8, True), ((32, 32, 32), 1, False)],
    ids=["8_planes", "6_planes", "4_planes", "8_planes_of_8_ranks",
         "one_device"])
def test_a_slab_thinner_than_two_halos_holds_no_window(grid, ranks, holds):
    """No window where there is no neighbour or the slab is thinner than
    2 H: today's path alone, no `cond` in the program, no ledger row."""
    from scenery_insitu_tpu import obs

    obs.clear_ledger()
    flow = vx.VortexFlow.init_ring(grid)
    if ranks == 1:
        program, u = vx.frame_program(), flow.u
    else:
        mesh = make_mesh(ranks)
        program = vx.frame_program(mesh, mesh.axis_names[0])
        u = jax.device_put(flow.u, sharded(mesh)[0])
    assert bool(vx.window_halo(grid[0] // ranks, ranks)) == holds
    jaxpr = str(jax.make_jaxpr(program, static_argnums=2)(
        u, flow.params, 1))
    assert ("cond[" in jaxpr) == holds
    assert ("all_gather" in jaxpr) == (ranks > 1)
    assert ("ppermute" in jaxpr) == holds
    _, _, windows = program(u, flow.params, 1)
    assert np.asarray(windows)[0, 1:].tolist() == (
        [4.0, 1.0] if holds else [0.0, 0.0])
    assert window_rows() == []


# ---------------------------------------------- the windowed kernel
#
# On a TPU the windowed branch is a Pallas kernel (`sim/pallas_backtrace`)
# that blends the eight corners from shifted copies of the window staged
# in VMEM, with no gather and no cells; here it runs in interpret mode,
# steered into the branch by the test. It wants whole tiles: X a multiple
# of 128 lanes, Y of 8 rows, the slab of its 8-plane z-blocks.

KERNEL_GRID = (64, 32, 128)
KERNEL_ROLLS = dict(ROLLS, core_on_the_y_wrap=(0, 9, 0),
                    core_on_the_x_wrap=(0, 0, 36),      # 64 + 28.2 + 36
                    cores_on_every_wrap=(-24, 9, 36))


def kernel_start(roll, scale=1.0):
    u0, params = seeded_start(KERNEL_GRID, SEEDS[1])
    return jnp.roll(u0, roll, (1, 2, 3)) * scale, params


@pytest.mark.parametrize("ranks", [4, 8])
@pytest.mark.parametrize("roll", KERNEL_ROLLS.values(),
                         ids=KERNEL_ROLLS.keys())
def test_the_windowed_kernel_equals_the_gather(roll, ranks, monkeypatch):
    """The kernel (interpret mode) against the XLA window and the
    one-device back-trace: the same eight corners and weights summed in
    another order, so equal to 2e-6 of the largest speed (a few ulp)."""
    from scenery_insitu_tpu.sim import pallas_backtrace

    mesh = make_mesh(ranks)
    u0, params = kernel_start(roll, scale=1.4)
    u_sh, _ = sharded(mesh)
    one, one_w = jax.jit(vx.advect_window)(u0, params.dt)
    assert 2.5 < float(one_w[0]) < 3.0          # near the bound of H = 4
    xla, _ = advect_on(mesh)(jax.device_put(u0, u_sh), params.dt)
    assert "pallas_call" not in str(jax.make_jaxpr(advect_on(mesh))(
        u0, params.dt))
    monkeypatch.setattr(vx, "_window_kernel", pallas_backtrace.fits)
    kernel = advect_on(mesh)
    assert "pallas_call" in str(jax.make_jaxpr(kernel)(u0, params.dt))
    got, window = kernel(jax.device_put(u0, u_sh), params.dt)
    assert np.asarray(window).tolist() == [float(one_w[0]), 4.0, 1.0]
    atol = 2e-6 * float(jnp.abs(one).max())
    np.testing.assert_array_equal(np.asarray(xla), np.asarray(one))
    np.testing.assert_allclose(np.asarray(got), np.asarray(one), rtol=0,
                               atol=atol)
    assert np.abs(np.asarray(got) - np.asarray(u0)).max() > 1.0


@pytest.mark.parametrize("scale", [0.02, 1.0, 1.4],
                         ids=["quiet", "as_seeded", "near_the_bound"])
def test_the_kernel_walks_its_tiles_own_ranges(scale):
    """What the kernel's loops run over: per tile of 8 rows the offsets
    from the smallest low corner to the largest high corner — two or
    three an axis where the flow is quiet (three where the tile's points
    move both ways: a low corner at -1 and one at 0), wider through a
    core, never past the halo under the bound — and a blend over just
    those offsets is the whole blend."""
    from scenery_insitu_tpu.sim import pallas_backtrace as pb

    halo, planes, first = 4, 16, 16             # rank 1 of 4
    u0, params = kernel_start((0, 9, 36), scale)
    d, h, w = KERNEL_GRID
    own = u0[:, first:first + planes]
    window = u0[:, first - halo:first + planes + halo]
    low, frac = jax.jit(vx._back_trace, static_argnums=(2, 3))(
        own, params.dt, first, d)
    inside = (jnp.mod(low[0] - 1 - first + halo, d),) + tuple(low[1:])
    rel = pb.relative(inside, halo)
    ranges = np.asarray(pb.tile_ranges(rel)).reshape(planes, h // 8, 6)
    spans = ranges[..., 1::2] - ranges[..., ::2] + 1
    assert spans.min() in (2, 3)
    assert (spans <= 3).all() == (scale == 0.02)
    assert ranges[..., 0].min() >= -(halo - 1)
    assert ranges[..., 1].max() <= halo - 1
    if scale == 1.4:
        assert spans.max() >= 5
    got = pb.back_trace(window, inside, tuple(frac), halo=halo,
                        interpret=True)
    want = vx._gather_blend(vx._wrap_pad(window, z=False), list(inside),
                            frac)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-6 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("planes,halo,y,x,takes", [
    (64, 16, 256, 256, True), (16, 4, 32, 128, True), (8, 4, 16, 128, True),
    (16, 4, 32, 40, False), (12, 4, 32, 128, False),
    (16, 4, 30, 128, False), (128, 32, 512, 512, False)],
    ids=["the_cell", "test_slab", "one_block", "ragged_lanes",
         "ragged_blocks", "ragged_rows", "past_vmem"])
def test_which_slabs_the_kernel_takes(planes, halo, y, x, takes):
    from scenery_insitu_tpu.sim import pallas_backtrace

    assert pallas_backtrace.fits(planes, halo, y, x) == takes
    # off a TPU the window is read by the XLA gather whatever the shape
    assert not vx._window_kernel(planes, halo, y, x)


# ---------------------------------------------------------- the session

OVERRIDES = ("sim.kind=vortex", "runtime.dataset=vortex",
             "sim.grid=[32,32,32]", "sim.steps_per_frame=1", "sim.dt=0.1",
             "slicer.engine=mxu", "vdi.adaptive_mode=temporal",
             "vdi.max_supersegments=8",
             "composite.max_output_supersegments=8", "mesh.num_devices=4")


def session(*extra):
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.session import InSituSession

    got = []
    sess = InSituSession(
        FrameworkConfig().with_overrides(*OVERRIDES, *extra),
        sinks=[lambda index, payload: got.append(payload)])
    return sess, got


@pytest.mark.parametrize("recorded", [False, True],
                         ids=["obs_off", "obs_on"])
def test_session_keeps_placements_and_compiles_nothing_after_frame_0(
        recorded):
    from chipbench.harness import CompileMeter

    sess, got = session(*(["obs.enabled=true"] if recorded else []))
    u_sh, f_sh = sharded(sess.mesh)
    assert float(sess.sim.state.params.dt) == pytest.approx(0.1)
    assert sess.sim.state.u.sharding.is_equivalent_to(u_sh, 4)
    assert sess.sim.field.sharding.is_equivalent_to(f_sh, 3)
    meter = CompileMeter()
    try:
        sess.run(1)
        first = meter.snapshot()["requests"]
        steps0 = sess.obs.counters.get("compile_step", 0)
        fields = [np.asarray(sess.sim.field)]
        for _ in range(3):
            sess.run(1)
            assert sess.sim.state.u.sharding.is_equivalent_to(u_sh, 4)
            assert sess.sim.field.sharding.is_equivalent_to(f_sh, 3)
            fields.append(np.asarray(sess.sim.field))
        assert meter.snapshot()["requests"] == first
        assert sess.obs.counters.get("compile_step", 0) == steps0
    finally:
        meter.close()
    assert [p["frame"] for p in got] == [0, 1, 2, 3]
    assert all(np.abs(a - b).max() > 1e-4
               for a, b in zip(fields, fields[1:]))      # it advances
    table = sess.obs.hlo_scopes.get("jit_vortex_frame", {})
    if recorded:
        assert {"sim_advect", "sim_project", "sim_field"} <= set(
            table.values())
    else:
        assert not table


def test_no_eager_op_between_the_sim_program_and_the_state():
    """One program per frame: inside the `sim` span the adapter calls the
    frame program once, and the state's u and the field it hands the
    render step ARE that call's outputs (the very arrays), so no eager op
    made or placed either. Replacing the state drops the stale field."""
    sess, _ = session("obs.enabled=true")
    sess.run(1)
    calls = []

    def spy(program):
        def run(state, n):
            calls.append(program(state, n))
            return calls[-1]
        return run

    sess.sim._advance = spy(sess.sim._advance)
    sess.run(2)
    assert len(calls) == 2
    assert sess.sim.state.u is calls[-1][0].u
    assert sess.sim.field is calls[-1][1]
    spans = [e for e in sess.obs.events
             if e["type"] == "span" and e["name"] == "sim"]
    assert len(spans) == 3
    before = np.asarray(sess.sim.field)
    sess.sim.state = sess.sim.state._replace(u=sess.sim.state.u * 0.5)
    after = sess.sim.field      # rendered anew, by the program, not stale
    assert after.sharding.is_equivalent_to(sharded(sess.mesh)[1], 3)
    # |curl u| over its largest value does not change with a factor
    np.testing.assert_allclose(np.asarray(after), before, atol=1e-5)
    assert after is not calls[-1][1] and len(calls) == 2
