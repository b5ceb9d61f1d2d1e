"""The field source `sim_vortex`: the session's own vortex-ring solver
advances the velocity on the device and hands back the rendered field
(|curl u|, normalised), every frame, as ONE program
(`scenery_insitu_tpu/sim/vortex.py` `frame_program`).

What a field source owns is in `sim_gray_scott.py`'s docstring and in
chipbench/README.md ("A field source"). This one's parts:

- `--seed`: the rings' start velocity times (1 + `field_perturbation` *
  uniform(-1, 1)) BEFORE the initial projection, made on the device in
  the state's placement by the program's own `ring_velocity` and
  `project_divfree`: the same work on other data for every seed;
- kept for the comparison: the rendered field after frame 0 and the
  number of devices the state lives on;
- the plain reference: `chipbench/reference_vortex.py` (float64 on the
  host, independent of the program). The reference SESSION is not fed
  its start: it makes the seeded start as the timed session does (same
  program, same seed, same bits), so the decoded frames compare the
  render path alone, and the solver's agreement is the field
  comparison's. Fed the reference's start, which differs from the
  program's by the transforms' rounding (4e-6 voxels per unit time at
  32^3), the same two frames read 117-147 dB at the rehearsal size where
  they are equal bit for bit unfed: the frames' limit would then hold
  the solver's rounding, through thresholds that flip, not the kernels;
- the limit `limits.sim_atol` on the largest difference of the two
  fields, both in [0, 1]; the control holds the reference's state in
  bfloat16.

Read from the configuration: `shape.grid`, `shape.steps_per_frame` (or
the last `sim.steps_per_frame` override), `solver.dt`,
`solver.viscosity` (checked against the session's own parameters), from
the traffic file `field_perturbation`. A traffic file with
`pre_evolve_steps` is not served.
"""

import numpy as np

from chipbench import reference, reference_vortex
from chipbench.sources import sim_gray_scott

def frame0_steps(cell: dict) -> int:
    """The steps frame 0 takes, from the cell's files alone (as
    `sim_gray_scott` reads them)."""
    pre, steps = sim_gray_scott.frame0_steps(cell)
    if pre:
        raise ValueError("sim_vortex serves no traffic with "
                         "pre_evolve_steps")
    return steps


def seeded_start(grid, params, key, amplitude):
    """The session's start from the seed, by the program's own functions:
    rings, perturbation, projection."""
    import jax
    import jax.numpy as jnp

    from scenery_insitu_tpu.sim import vortex

    u = vortex.ring_velocity(grid)
    u = u * (1.0 + amplitude * jax.random.uniform(key, u.shape, jnp.float32,
                                                  -1.0, 1.0))
    return vortex.project_divfree(u, params, 0.0)


def build_session(cell: dict, overrides, seed: int, sink=None, viewer=None,
                  fed=None):
    """`InSituSession(cfg, sinks=[sink])` from config overrides; then the
    start is made from the seed on the device in the state's placement
    (`seeded_start`), for the reference session too (`fed` holds nothing
    for it: see above); the viewer becomes the in-process steering
    source."""
    import functools

    import jax

    from chipbench import harness
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.session import InSituSession
    from scenery_insitu_tpu.sim import vortex

    if not hasattr(vortex, "frame_program"):
        raise harness.BenchFailure(
            "this checkout's sim/vortex.py has no frame_program: the "
            "vortex configuration is not supported by it")
    conf, traf = cell["config_file"], cell["traffic_file"]
    cfg = FrameworkConfig().with_overrides(*overrides)
    sess = InSituSession(cfg, sinks=[sink] if sink else [])
    state = sess.sim.state
    solver = conf["solver"]
    have = (float(state.params.dt), float(state.params.viscosity))
    want = (float(np.float32(solver["dt"])),
            float(np.float32(solver["viscosity"])))
    if have != want:
        raise ValueError(f"the session's (dt, viscosity) {have} are not the "
                         f"configuration's {want}")
    u = jax.jit(functools.partial(seeded_start, tuple(cfg.sim.grid)),
                out_shardings=state.u.sharding)(
        state.params, reference.seed_key(seed),
        np.float32(traf["field_perturbation"]))
    sess.sim.state = state._replace(u=u)
    sess.steering = viewer
    return sess


def keep(sess) -> dict:
    """After frame 0: the field the frame was rendered from, on the host,
    and the number of devices the sim state lives on."""
    field = sess.sim.field
    return {"field0": np.asarray(field),
            "sim_devices": len(sess.sim.state.u.sharding.device_set)}


def wait(sess) -> None:
    """The event a window (and an unfetched reference frame) ends on: the
    last sim program has run."""
    import jax

    jax.block_until_ready(sess.sim.field)


def window_checks(cell: dict, kept: dict) -> list:
    ranks = cell["config_file"]["shape"]["ranks"]
    return [("sim_state_devices", kept["sim_devices"], ranks,
             kept["sim_devices"] == ranks)]


def plain_field0(cell: dict, seed: int, dtype: str = "float32"):
    """The rendered field after frame 0 by the plain reference, its state
    held in `dtype`."""
    conf = cell["config_file"]
    return reference_vortex.frame0(
        conf["shape"]["grid"], seed,
        cell["traffic_file"]["field_perturbation"], frame0_steps(cell),
        conf["solver"]["dt"], conf["solver"]["viscosity"], dtype)


def plain_reference(cell: dict, seed: int) -> dict:
    """The plain reference of what `keep` kept."""
    return {"field0": plain_field0(cell, seed)}


def compare(cell: dict, kept: dict, ref: dict) -> list:
    atol = cell["config_file"]["limits"]["sim_atol"]
    err = float(np.abs(kept["field0"] - ref["field0"]).max())
    return [("sim_field_frame0_max_abs_diff", err, atol, err <= atol)]


def rounded(cell: dict, seed: int, kept: dict) -> dict:
    """The control: the plain reference with its state held in bfloat16
    where the program's field would stand."""
    return dict(kept, field0=plain_field0(cell, seed, "bfloat16"))
