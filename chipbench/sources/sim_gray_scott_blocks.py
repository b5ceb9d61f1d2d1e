"""The field source `sim_gray_scott_blocks`: the session's own Gray-Scott
simulation on a grid whose state NO SINGLE DEVICE can hold (1024^3 on
four chips: u and v are 8.59 GB, a chip has 16), so nothing of the run —
start, seed, kept field, reference — is ever made whole on one device.

What a field source owns is in `sim_gray_scott.py`'s docstring and in
chipbench/README.md ("A field source"). This one's parts:

- the session is built as every cell's is; the program has to give its
  state birth on the mesh (`GrayScott.init` with a sharding: PR 41). A
  checkout that builds the start whole on one device runs out of memory
  in `InSituSession(...)`: that is reported as no result, with the
  allocator's own words;
- `--seed`: v times (1 + `field_perturbation` * n), n in [-1, 1) drawn
  per CELL from (seed, z, y, x) (`seeded`, below: the rule
  `chipbench/reference_gs_blocks.py` `draw` defines, written a second
  time here so that the session never runs the reference's code), inside
  one jitted program with the state's own placement as `out_shardings`:
  each rank makes the draw of its own planes from its own indices. The
  same cubes in the same places for every seed, so the same work on
  other data (`reference.perturb`'s one stream over the whole grid is
  what `sim_gray_scott` uses; a slab of it cannot be made alone);
- kept for the comparison: the field after frame 0, copied to the host
  shard by shard; the number of devices the state lives on; every
  device's `peak_bytes_in_use` (the allocator's high-water mark since
  the process began: construction, seeding and frame 0);
- the plain reference: `reference_gs_blocks.field_after`, the plain roll
  in z-blocks of `SLAB` planes with the frame's steps as halo, on the
  default device; the reference SESSION makes the same seeded start as
  the timed one (same program, same bits), as `sim_vortex`'s does;
- limits: `limits.sim_atol` on the largest difference of the two fields,
  `limits.rank_peak_bytes_max` on the fullest device's high-water mark;
  the control holds the reference's state in bfloat16.

A traffic file with `pre_evolve_steps` is not served.
"""

import numpy as np

from chipbench import reference_gs_blocks
from chipbench.sources import sim_gray_scott

# planes of one block of the reference: with 10 halo planes a side at
# 1024 x 1024, u and v of a block are 0.70 GB on the device
SLAB = 64


def frame0_steps(cell: dict) -> int:
    pre, steps = sim_gray_scott.frame0_steps(cell)
    if pre:
        raise ValueError("sim_gray_scott_blocks serves no traffic with "
                         "pre_evolve_steps")
    return steps


def seeded(v, keys, amplitude):
    """v times (1 + amplitude * n): n in [-1, 1) from 24 bits of
    mix(mix(index ^ keys[0]) ^ keys[1]), index = (z * H + y) * W + x in
    32 bits, mix = (h ^= h >> 16, h *= 0x7FEB352D, h ^= h >> 15,
    h *= 0x846CA68B, h ^= h >> 16): `reference_gs_blocks.draw`'s rule,
    from each cell's own index (iotas that fuse into the product)."""
    import jax
    import jax.numpy as jnp

    def mix(h):
        h = h ^ (h >> 16)
        h = h * jnp.uint32(0x7FEB352D)
        h = h ^ (h >> 15)
        h = h * jnp.uint32(0x846CA68B)
        return h ^ (h >> 16)

    _, h, w = v.shape
    z, y, x = (jax.lax.broadcasted_iota(jnp.uint32, v.shape, a)
               for a in range(3))
    index = (z * jnp.uint32(h) + y) * jnp.uint32(w) + x
    bits = mix(mix(index ^ keys[0]) ^ keys[1])
    n = ((bits >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -23)
         - jnp.float32(1.0))
    return v * (1.0 + amplitude * n)


def seed_keys(seed: int) -> np.ndarray:
    """The two 32-bit keys of `--seed` (up to 64 bits): one mix of each
    of its words, as `reference_gs_blocks.seed_keys` defines them."""
    def mix(h: int) -> int:
        h ^= h >> 16
        h = (h * 0x7FEB352D) & 0xFFFFFFFF
        h ^= h >> 15
        h = (h * 0x846CA68B) & 0xFFFFFFFF
        return h ^ (h >> 16)

    seed = int(seed)
    return np.asarray([mix((seed & 0xFFFFFFFF) ^ 0x9E3779B9),
                       mix(((seed >> 32) & 0xFFFFFFFF) ^ 0x85EBCA6B)],
                      np.uint32)


def build_session(cell: dict, overrides, seed: int, sink=None, viewer=None,
                  fed=None):
    """`InSituSession(cfg, sinks=[sink])` from config overrides; then v is
    seeded where it lies (`seeded`, `out_shardings` = the state's own),
    for the reference session too (`fed` holds nothing for it); the
    viewer becomes the in-process steering source."""
    import jax

    from chipbench import harness
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.session import InSituSession

    frame0_steps(cell)
    # every device's high-water mark before anything of this session is
    # built: the mark is the process's, and a process may build several
    # sessions one after the other (control.py)
    marks_before = dict(zip(jax.devices(), device_peaks(jax.devices())))
    cfg = FrameworkConfig().with_overrides(*overrides)
    try:
        sess = InSituSession(cfg, sinks=[sink] if sink else [])
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        raise harness.BenchFailure(
            f"this checkout cannot build the {tuple(cfg.sim.grid)} state "
            f"on {cfg.mesh.num_devices} device(s): out of memory in "
            f"InSituSession(...): {str(e).splitlines()[0][:300]}") from e
    state = sess.sim.state
    sess.sim.state = state._replace(v=jax.jit(
        seeded, out_shardings=state.v.sharding)(
            state.v, seed_keys(seed),
            np.float32(cell["traffic_file"]["field_perturbation"])))
    sess.steering = viewer
    sess.chipbench_marks_before = marks_before      # for `keep`
    return sess


def device_peaks(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def to_host(field) -> np.ndarray:
    """A device array on the host, copied shard by shard into its place:
    no device gathers what the others hold."""
    out = np.empty(field.shape, field.dtype)
    for shard in field.addressable_shards:
        out[shard.index] = np.asarray(shard.data)
    return out


def keep(sess) -> dict:
    """After frame 0: the field the frame was rendered from, on the host;
    the number of devices the sim state lives on; and every one of those
    devices' `peak_bytes_in_use` so far, beside what it was before the
    session was built (None where the platform's allocator keeps no
    statistics)."""
    field = sess.sim.field
    devices = sorted(field.sharding.device_set, key=lambda d: d.id)
    return {"field0": to_host(field), "sim_devices": len(devices),
            "platform": devices[0].platform,
            "rank_peaks": device_peaks(devices),
            "rank_peaks_before": [sess.chipbench_marks_before.get(d)
                                  for d in devices]}


def wait(sess) -> None:
    """The event a window (and an unfetched reference frame) ends on: the
    last sim advance has run."""
    import jax

    jax.block_until_ready(sess.sim.field)


def window_checks(cell: dict, kept: dict) -> list:
    """The state lives on all the ranks, and no device's high-water mark
    (construction, seeding, frame 0) passed `limits.rank_peak_bytes_max`.
    The mark is the process's: where an earlier session of this process
    (control.py reads several) left it above the limit and this one did
    not raise it, this one stayed under that and is passed, and said so.
    Off the TPU the allocator keeps no statistics: said, not failed."""
    conf = cell["config_file"]
    ranks, limit = conf["shape"]["ranks"], conf["limits"][
        "rank_peak_bytes_max"]
    peaks, before = kept["rank_peaks"], kept["rank_peaks_before"]
    if None in peaks:
        mem = ("rank_peak_bytes_max", f"not kept on {kept['platform']}",
               limit, kept["platform"] != "tpu")
    elif max(peaks) > limit and peaks == before:
        mem = ("rank_peak_bytes_max", f"{max(peaks)}, an earlier session's "
               "mark that this one did not raise", limit, True)
    else:
        mem = ("rank_peak_bytes_max", max(peaks), limit, max(peaks) <= limit)
    return [("sim_state_devices", kept["sim_devices"], ranks,
             kept["sim_devices"] == ranks), mem]


def plain_field0(cell: dict, seed: int, dtype: str = "float32"):
    """The field after frame 0 by the plain roll in blocks, its state held
    in `dtype`."""
    return reference_gs_blocks.field_after(
        cell["config_file"]["shape"]["grid"], seed,
        cell["traffic_file"]["field_perturbation"], frame0_steps(cell),
        SLAB, dtype)


def plain_reference(cell: dict, seed: int) -> dict:
    """The plain reference of what `keep` kept."""
    return {"field0": plain_field0(cell, seed)}


def compare(cell: dict, kept: dict, ref: dict) -> list:
    """The largest difference of the two fields, block by block (a
    difference of the whole grid would be a third 4.3 GB array)."""
    atol = cell["config_file"]["limits"]["sim_atol"]
    a, b = kept["field0"], ref["field0"]
    err = max(float(np.abs(a[z0:z0 + n] - b[z0:z0 + n]).max())
              for z0, n in reference_gs_blocks.blocks(a.shape[0], SLAB))
    return [("sim_field_frame0_max_abs_diff", err, atol, err <= atol)]


def rounded(cell: dict, seed: int, kept: dict) -> dict:
    """The control: the plain roll in blocks with its state held in
    bfloat16 where the program's field would stand."""
    return dict(kept, field0=plain_field0(cell, seed, "bfloat16"))
