"""The field source `sim_gray_scott`: the session's own Gray-Scott
simulation advances the field on the device, every frame.

A field source owns what depends on where the rendered field comes from,
and nothing else (chipbench/README.md, "A field source"): how the session
gets its field and what `--seed` does to it, the part of the field a run
keeps for the comparison, the event a window ends on, its window checks,
the plain reference of what was kept with its comparison and limits, and
how the reference session is fed the same field. The harness finds this
file by the configuration's `field_source` (absent: `sources.DEFAULT`) and
names none of it.

Read from the traffic file: `field_perturbation` (what the seed does) and
`pre_evolve_steps` (default 0): steps the program's own sim takes in
set-up, before frame 0, `shape.steps_per_frame` at a time (the step count
is a static argument of the sim program and its schedule is walked
statically, so one call of 500 steps would compile 125 kernel sites where
50 calls of 10 run a program the cache already holds); the plain roll of
the reference takes the same steps.
"""

import numpy as np

from chipbench import reference

STEPS_KEY = "sim.steps_per_frame="


def steps_per_frame(overrides, default: int) -> int:
    """The steps one frame takes: the last `sim.steps_per_frame` among the
    overrides the session is built from (a traffic file's stand after the
    configuration's), else the configuration's `shape.steps_per_frame`."""
    steps = default
    for o in overrides:
        if o.startswith(STEPS_KEY):
            steps = int(o[len(STEPS_KEY):])
    return steps


def frame0_steps(cell: dict) -> tuple:
    """(set-up steps, steps of frame 0) from the cell's files alone."""
    conf, traf = cell["config_file"], cell["traffic_file"]
    return (int(traf.get("pre_evolve_steps", 0)), steps_per_frame(
        conf["overrides"] + traf.get("overrides", []),
        conf["shape"]["steps_per_frame"]))


def build_session(cell: dict, overrides, seed: int, sink=None, viewer=None,
                  fed=None):
    """`InSituSession(cfg, sinks=[sink])` from config overrides, as
    chip_smoke.py builds it; then the session's own Gray-Scott start is
    perturbed on the device from the seed (`reference.perturb`, keeping
    the state's placement on the mesh) and advanced by the session's own
    sim through the traffic's `pre_evolve_steps`; the viewer becomes the
    in-process steering source. `fed` (what `plain_reference` returned) may hold
    `start`: the state at the start of frame 0 by the plain roll, which
    the reference session then takes instead of evolving its own."""
    import jax

    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.session import InSituSession

    traf = cell["traffic_file"]
    cfg = FrameworkConfig().with_overrides(*overrides)
    sess = InSituSession(cfg, sinks=[sink] if sink else [])
    state = sess.sim.state
    start = fed.pop("start", None) if fed else None
    if start is not None:
        sess.sim.state = state._replace(
            u=jax.device_put(start[0], state.u.sharding),
            v=jax.device_put(start[1], state.v.sharding))
    else:
        sess.sim.state = state._replace(v=jax.jit(
            reference.perturb, out_shardings=state.v.sharding)(
                state.v, reference.seed_key(seed),
                np.float32(traf["field_perturbation"])))
        pre = int(traf.get("pre_evolve_steps", 0))
        if pre:
            chunk = cell["config_file"]["shape"]["steps_per_frame"]
            if pre % chunk:
                raise ValueError(f"pre_evolve_steps {pre} is not a multiple "
                                 f"of shape.steps_per_frame {chunk}")
            for _ in range(pre // chunk):
                sess.sim.advance(chunk)
    sess.steering = viewer
    return sess


def keep(sess) -> dict:
    """After frame 0: the field the frame was rendered from, on the host,
    and the number of devices the sim state lives on."""
    field = sess.sim.field
    return {"field0": np.asarray(field),
            "sim_devices": len(field.sharding.device_set)}


def wait(sess) -> None:
    """The event a window (and an unfetched reference frame) ends on: the
    last sim advance has run."""
    import jax

    jax.block_until_ready(sess.sim.field)


def window_checks(cell: dict, kept: dict) -> list:
    ranks = cell["config_file"]["shape"]["ranks"]
    return [("sim_state_devices", kept["sim_devices"], ranks,
             kept["sim_devices"] == ranks)]


def plain_roll(cell: dict, seed: int, dtype: str = "float32") -> tuple:
    """By the plain roll, from the same seed through the same set-up
    steps: the (u, v) frame 0 starts from, on the device (None without
    set-up steps), and the field after frame 0, f32 on the host."""
    import jax.numpy as jnp

    shape, traf = cell["config_file"]["shape"], cell["traffic_file"]
    pre, steps = frame0_steps(cell)
    u, v = reference.gray_scott_start(shape["grid"], seed,
                                      traf["field_perturbation"])
    start = None
    if pre:
        start = u, v = reference.gray_scott_steps(u, v, pre, dtype)
    v = reference.gray_scott_steps(u, v, steps, dtype)[1]
    return start, np.asarray(v.astype(jnp.float32))


def plain_reference(cell: dict, seed: int) -> dict:
    """The plain reference of what `keep` kept; `start` (host arrays) where
    there were set-up steps, for `build_session` to feed the reference
    session: 500 more steps of the program's own roll would take as long
    as the plain roll's (about 19 s at 512^3) and compare nothing more."""
    start, field0 = plain_roll(cell, seed)
    out = {"field0": field0}
    if start is not None:
        out["start"] = tuple(np.asarray(x) for x in start)
    return out


def compare(cell: dict, kept: dict, ref: dict) -> list:
    atol = cell["config_file"]["limits"]["sim_atol"]
    err = float(np.abs(kept["field0"] - ref["field0"]).max())
    return [("sim_field_frame0_max_abs_diff", err, atol, err <= atol)]


def rounded(cell: dict, seed: int, kept: dict) -> dict:
    """The control: the plain roll computed in bfloat16 where the
    program's field would stand."""
    return dict(kept, field0=plain_roll(cell, seed, "bfloat16")[1])
