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
    u1, field = vx.frame_program()(u0, params, 1)
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
    _, field = vx.frame_program()(u0, params, 1)
    low = reference_vortex.frame0(grid, SEEDS[0], AMPLITUDE, 1,
                                  dtype="bfloat16")
    assert np.abs(np.asarray(field) - low).max() > 100 * FIELD_ATOL
    hold = lambda u: u.astype(jnp.bfloat16).astype(jnp.float32)
    _, own = vx.frame_program()(hold(u0), params, 1)
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
    one_u, one_f = vx.frame_program()(u0, params, steps)
    u, field = vx.frame_program(mesh, mesh.axis_names[0])(
        jax.device_put(u0, u_sh), params, steps)
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
    u1, _ = vx.frame_program()(u0, params, 1)
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
    u, field = vx.frame_program()(flow.u, flow.params, 2)
    two = vx.multi_step(flow, 2)
    np.testing.assert_allclose(np.asarray(u), np.asarray(two.u), atol=1e-5)
    np.testing.assert_allclose(np.asarray(field), np.asarray(two.field),
                               atol=1e-6)


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
