"""The field source `raw_dataset`: no simulation; the session renders a raw
volume file by name (≅ upstream's VolumeFromFileExample), loaded in
z-slabs straight to the device at the file's dtype and held resident
(`scenery_insitu_tpu/runtime/session.py` `DatasetVolumeAdapter`).

What a field source owns is in `sim_gray_scott.py`'s docstring and in
chipbench/README.md ("A field source"); this one's protocol, data and
limits are in chipbench/README_dataset.md. Its parts:

- the data are not the scan (it is not available here) but CT-like by
  construction (`slab`, `assumed` in the configuration): a coiled tube,
  the "snake", of tissue over the transfer function's knee with a denser
  core and ribs along its length, in air whose noise stays under the
  knee. The geometry is the same for every `--seed` (so every seed does
  the same work: a coil turned by the seed would change what the march
  skips, and the frame time with it); the seed draws the air's noise and
  the tissue's grain. Made on the device slab by slab and written to
  `<checkout>/.chipbench/dataset/<cell>-<seed>/<name>.raw` in set-up; the
  session then reads that file as a deployment reads its scan;
- kept for the comparison: the bytes resident on the device after frame
  0 (on the host, at the file's dtype), frame 0 itself (a sink of the
  source's own keeps it: the starting camera, before any steering), the
  session's counters;
- the plain reference: the same bytes regenerated, and for the image
  `chipbench/reference_raycast.py` on the widened f32 volume. The
  reference SESSION is fed that widened volume (value / 255 in float32,
  as `core/volume.load_raw` widens) through an adapter of this file;
- limits: `field_max_abs_diff` 0 (the resident bytes ARE the file's),
  `limits.raycast_psnr_floor_db` (decoded frame 0 against the raycast),
  `limits.share_over_knee` (the data's construction, reported);
- the control (`rounded`): the bytes held in the nearest precision below
  the file's (the lowest bit dropped) and the raycast computed in
  bfloat16.

Read from the configuration: `shape.grid`, `shape.dtype`,
`transfer_function.alpha`, `data`; from the traffic file `field_noise`.
"""

import os
import shutil

import numpy as np

from chipbench import arith, reference, reference_raycast

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def slab(z0, key, planes: int, dims_dhw, data: dict, noise: dict, dtype):
    """Planes [z0, z0 + planes) of the volume, integers of `dtype`, from
    the configuration's `data` (all lengths as shares of the volume's
    extents) and the traffic's `field_noise` (levels as shares of the
    dtype's range): a helix about the z axis, radius `coil_radius` of the
    x extent, `turns` turns between `z_range`; a point's distance to the
    tube's centre line is taken in the plane through it that holds the
    axis (radial offset, and the offset along z to the nearest turn),
    which is exact for a flat pitch and close for this one."""
    import jax
    import jax.numpy as jnp

    d, h, w = dims_dhw
    top = float(np.iinfo(np.dtype(dtype)).max)
    z = (z0 + jnp.arange(planes, dtype=jnp.float32) + 0.5)[:, None, None]
    y = (jnp.arange(h, dtype=jnp.float32) + 0.5)[None, :, None] - h / 2
    x = (jnp.arange(w, dtype=jnp.float32) + 0.5)[None, None, :] - w / 2
    za, zb = (s * d for s in data["z_range"])
    pitch = (zb - za) / data["turns"]
    r = jnp.sqrt(x * x + y * y)
    phi = jnp.arctan2(y, x) / (2 * np.pi) + 0.5         # turns, (0, 1]
    # the centre line passes angle phi at heights za + (phi + n) * pitch
    t = (z - za) / pitch - phi
    n = jnp.clip(jnp.round(t), 0, data["turns"] - 1)
    dz = (t - n) * pitch
    dist = jnp.sqrt((r - data["coil_radius"] * w) ** 2 + dz * dz)
    a = data["tube_radius"] * w
    arc = (n + phi) * data["ribs_per_turn"]
    tissue = (data["tissue"] + data["rib_depth"]
              * jnp.cos(2 * np.pi * arc))
    tissue = jnp.where(dist < data["core_radius"] * a, data["core"], tissue)
    k1, k2 = jax.random.split(jax.random.fold_in(key, z0))
    shape = (planes, h, w)
    air = noise["air_low"] + (noise["air_high"] - noise["air_low"]) \
        * jax.random.uniform(k1, shape)
    grain = noise["grain"] * (jax.random.uniform(k2, shape) - 0.5)
    # a soft skin two voxels wide, as a scan's edges are
    inside = jnp.clip((a - dist) / 2.0 + 0.5, 0.0, 1.0)
    value = air + inside * (tissue + grain - air)
    return jnp.round(jnp.clip(value, 0.0, 1.0) * top).astype(dtype)


def generate(cell: dict, seed: int, planes: int = 64):
    """Yields (z0, slab on the device) over the volume, from the seed."""
    import functools

    import jax

    conf, traf = cell["config_file"], cell["traffic_file"]
    dims, dtype = tuple(conf["shape"]["grid"]), conf["shape"]["dtype"]
    key = reference.seed_key(seed)
    make = {}
    for z0 in range(0, dims[0], planes):
        n = min(planes, dims[0] - z0)
        if n not in make:
            make[n] = jax.jit(functools.partial(
                slab, planes=n, dims_dhw=dims, data=conf["data"],
                noise=traf["field_noise"], dtype=dtype))
        yield z0, make[n](np.float32(z0), key)


def dataset_name(cell: dict) -> str:
    """The `runtime.dataset` the cell's session is built with."""
    key = "runtime.dataset="
    return [o[len(key):] for o in cell["config_file"]["overrides"]
            if o.startswith(key)][-1]


def write_file(cell: dict, seed: int) -> str:
    """The seeded volume as a raw file under the checkout's `.chipbench/`;
    returns its directory."""
    home = os.path.join(ROOT, ".chipbench", "dataset",
                        f"{cell['name']}-{seed}")
    shutil.rmtree(home, ignore_errors=True)
    os.makedirs(home)
    with open(os.path.join(home, dataset_name(cell) + ".raw"), "wb") as f:
        for _, part in generate(cell, seed):
            np.asarray(part).tofile(f)
    return home


class Widened:
    """What the reference session renders: the same volume widened to
    normalised float32 on the device, as `core/volume.load_raw` widens
    (value / max), behind the facade the session takes as `sim=`."""

    kind, static = "dataset", True

    def __init__(self, field):
        import jax.numpy as jnp

        top = float(np.iinfo(field.dtype).max)
        self.field = jnp.asarray(field).astype(jnp.float32) / top

    def advance(self, n: int) -> None:
        pass


def build_session(cell: dict, overrides, seed: int, sink=None, viewer=None,
                  fed=None):
    """`InSituSession(cfg, sinks=[sink])` from config overrides. The timed
    session is handed the path of the file made from the seed and loads it
    itself (by the dims and dtype tables where the configuration's grid is
    the table's, else by the configuration's: a rehearsal size); a sink of
    the source's own, first in the list, keeps frame 0. The reference
    session (`fed`: what `plain_reference` returned) is fed the widened
    volume instead. The viewer becomes the in-process steering source."""
    from chipbench import harness
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.core import volume
    from scenery_insitu_tpu.runtime import session

    if not hasattr(session, "DatasetVolumeAdapter"):
        raise harness.BenchFailure(
            "no field source: this checkout's runtime/session.py has no "
            "DatasetVolumeAdapter, the raw-dataset configuration is not "
            "supported by it")
    shape = cell["config_file"]["shape"]
    kept = {}

    def keep_frame0(index: int, payload: dict) -> None:
        if payload["frame"] == 0:
            kept.update(vdi_color=payload["vdi_color"],
                        vdi_depth=payload["vdi_depth"],
                        view=np.asarray(payload["meta"].view))

    sinks = ([keep_frame0, sink] if sink else [])
    if fed is not None:
        cfg = FrameworkConfig().with_overrides(*overrides)
        sim = Widened(fed["field0"])
    else:
        home = write_file(cell, seed)
        cfg = FrameworkConfig().with_overrides(
            *overrides, f"runtime.data_dir={home}")
        name = cfg.runtime.dataset.lower()
        dims = tuple(reversed(shape["grid"]))
        sim = None
        if (volume.DATASET_DIMS_XYZ.get(name) != dims or np.dtype(
                volume.DATASET_DTYPES[name]) != np.dtype(shape["dtype"])):
            sim = session.DatasetVolumeAdapter(cfg, dims_xyz=dims,
                                               dtype=shape["dtype"])
    sess = session.InSituSession(cfg, sim=sim, sinks=sinks)
    if fed is None:     # read and resident: the file has done its part
        shutil.rmtree(cfg.runtime.data_dir, ignore_errors=True)
    sess.steering = viewer
    sess.chipbench_frame0 = kept
    return sess


def keep(sess) -> dict:
    """After frame 0: the bytes resident on the device, on the host at
    their own dtype; frame 0 as the source's sink kept it; the session's
    counters (the object: they are read when the window is over)."""
    return {"field0": np.asarray(sess.sim.field),
            "frame0": sess.chipbench_frame0,
            "counters": sess.obs.counters}


def wait(sess) -> None:
    """No simulation runs beside the frames: nothing to wait for."""


def share_over_knee(cell: dict, field: np.ndarray) -> float:
    """The share of voxels whose normalised value lies over the transfer
    function's knee (its first point of non-zero slope)."""
    conf = cell["config_file"]
    pts = conf["transfer_function"]["alpha"]
    knee = max(x for x, a in pts if a == 0.0)
    level = knee * float(np.iinfo(field.dtype).max)
    return float(np.mean([np.count_nonzero(p > level) / p.size
                          for p in field]))


def window_checks(cell: dict, kept: dict) -> list:
    """The volume is resident once, at the file's dtype and size; where
    the run recorded (a traced run) no instruction of a frame's step
    wrote a copy of it; the data are what the configuration says."""
    from chipbench import arith_dataset

    conf = cell["config_file"]
    want = arith_dataset.volume_bytes(conf["shape"])
    held = kept["counters"].get("volume_resident_bytes")
    field = kept["field0"]
    out = [("volume_resident_bytes", held, want, held == want),
           ("field_dtype", field.dtype.name, conf["shape"]["dtype"],
            field.dtype == np.dtype(conf["shape"]["dtype"]))]
    copies = kept["counters"].get("volume_copies_per_frame")
    if copies is not None:
        out.append(("volume_copies_per_frame", copies, 0, copies == 0))
    lo, hi = conf["limits"]["share_over_knee"]
    share = share_over_knee(cell, field)
    out.append(("share_of_voxels_over_the_knee", share, f"{lo}..{hi}",
                lo <= share <= hi))
    return out


def plain_reference(cell: dict, seed: int) -> dict:
    """The plain reference of what `keep` kept of the field: the bytes
    regenerated from the seed."""
    return {"field0": np.concatenate(
        [np.asarray(part) for _, part in generate(cell, seed)])}


def raycast(cell: dict, field: np.ndarray, dtype: str = "float32"):
    """`reference_raycast` of the widened volume at the starting camera
    (the traffic's base eye: the session's default)."""
    import jax.numpy as jnp

    conf = cell["config_file"]
    ni, nj = arith.intermediate_grid(conf["shape"])
    top = float(np.iinfo(field.dtype).max)
    return reference_raycast.render(
        jnp.asarray(field).astype(jnp.float32) / top,
        cell["traffic_file"]["steering"]["base_eye"], ni, nj,
        conf["transfer_function"]["alpha"], dtype)


def compare(cell: dict, kept: dict, ref: dict) -> list:
    limits = cell["config_file"]["limits"]
    # in slabs of planes: the difference of two u8 volumes at once would
    # hold the volume again, twice as wide
    err = (max(int(np.abs(a.astype(np.int32) - b).max())
               for a, b in zip(kept["field0"], ref["field0"]))
           if kept["field0"].shape == ref["field0"].shape else "shapes")
    out = [("field_max_abs_diff", err, 0, err == 0)]
    floor = limits["raycast_psnr_floor_db"]
    image = kept.get("frame0_image")
    if image is None and kept["frame0"]:
        image = reference.decode(kept["frame0"]["vdi_color"],
                                 kept["frame0"]["vdi_depth"])
    if image is None:
        return out + [("raycast_psnr_dB_frame0", "not delivered", floor,
                       False)]
    q = reference.psnr(raycast(cell, ref["field0"]), image)
    return out + [("raycast_psnr_dB_frame0", q, floor, q >= floor)]


def rounded(cell: dict, seed: int, kept: dict) -> dict:
    """The control: the bytes in the nearest precision below the file's
    (the lowest bit dropped) where the resident ones would stand, and the
    raycast computed in bfloat16 where the decoded frame would."""
    field = plain_reference(cell, seed)["field0"]
    return dict(kept, field0=field & ~field.dtype.type(1),
                frame0_image=raycast(cell, field, "bfloat16"))
