"""The field source `raw_dataset_wide`: `raw_dataset` for a file wider than
a byte and too large to be widened twice (`beechnut-u16-1chip`: 1024 x
1024 x 1546 u16, 3.24 GB; 6.48 GB as float32).

It LOADS `raw_dataset.py`'s code (the accepted source: data, file,
session, `keep`, `plain_reference`, `compare`, the `rounded` control) and
differs only where this size, this dtype and this transfer function force
it (chipbench/README_dataset16.md):

- the widened float32 volume the plain raycast takes is made in ONE fused
  pass on the device (`widened`): the accepted source's eager
  `asarray(u16).astype(f32) / top` holds the u16 array and two float32
  ones at its worst, 16.2 GB of a 16 GB chip;
- the reference session is fed the resident bytes at their own dtype
  (`Native`): the widened volume (6.48 GB) with the bf16 operand the step
  hoists out of its chunk loop (3.24 GB) and the XLA fold's working set
  does not fit beside the frames, and that bf16 operand would keep 8 of
  the 16 bits. The decoded frames (`psnr_floor_db`) then compare the
  fold kernel with the XLA fold over the SAME march, as in the
  Gray-Scott cells: they hold the fold, not the march;
- so the march's own arithmetic is held to `reference_raycast` (f32
  gathers, nothing of the program) TWICE, by `raycast_psnr_floor_db`:
  at frame 0 (the starting camera) as in `raw_dataset`, and at the first
  STEERED frame of the warm-up, at the pose it was rendered from (a sink
  of this source keeps it with its view matrix), so that a fault of the
  march that depends on the pose is seen too;
- a program that says it resamples a chunk of the file's dtype as ONE
  matmul operand (`ops/slicer.operand_planes`, the rule behind its
  counter `march_operand_planes`; PR 49's parent has no such rule and
  takes f32 operands, one bf16 pass on a TPU) is refused before any data
  is made: it keeps 8 of the 16 bits, at fewer matmul passes. That is
  another result, which `raycast_psnr_floor_db` fails through the
  harness's own comparison (the parent's tree run with this look taken
  out: 70.7-71.1 dB under the floor of 73, at 2.09 frames/s; README), and
  a parent that ends `correct: false` after four minutes is no baseline
  for the driver to compare a correct change with;
- the data check: Beechnut's opacity is a TENT (0 up to 0.43, 0.321 at
  0.457, 0 again from 0.494), so "over the knee" (the last zero of the
  table: 1.0) says nothing; `share_of_voxels_in_the_tf_support` counts the
  voxels whose value lies where the opacity is not zero
  (`limits.share_in_tf_support`).

Read from the configuration: what `raw_dataset` reads, and
`limits.share_in_tf_support`.
"""

import os

import numpy as np

from chipbench import harness

_BASE = harness.load_file("source", os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "raw_dataset.py"))


def widened(field):
    """`field` (integers, host or device) as normalised float32 on the
    device, value / max as `core/volume.load_raw` widens, in one fused
    pass: the integers and ONE float32 array are alive at its worst."""
    import jax
    import jax.numpy as jnp

    top = float(np.iinfo(field.dtype).max)
    return jax.jit(lambda x: x.astype(jnp.float32) / top)(jnp.asarray(field))


class Native:
    """What the reference session renders: the same bytes at the file's
    dtype, behind the facade the session takes as `sim=`."""

    kind, static = "dataset", True

    def __init__(self, field):
        import jax.numpy as jnp

        self.field = jnp.asarray(field)

    def advance(self, n: int) -> None:
        pass


def raycast(cell: dict, field, dtype: str = "float32", eye=None):
    """`reference_raycast` of the widened volume from `eye` (None: the
    starting camera, the traffic's base eye: the session's default).
    `field`: the file's integers, or the volume already widened."""
    from chipbench import arith, reference_raycast

    conf = cell["config_file"]
    ni, nj = arith.intermediate_grid(conf["shape"])
    if eye is None:
        eye = cell["traffic_file"]["steering"]["base_eye"]
    volume = field if field.dtype == np.float32 else widened(field)
    return reference_raycast.render(
        volume, eye, ni, nj, conf["transfer_function"]["alpha"], dtype)


def tf_support(alpha_points) -> list:
    """The open value intervals on which the opacity polyline is not zero:
    [(0.43, 0.494)] for Beechnut's tent."""
    out = []
    for (x0, a0), (x1, a1) in zip(alpha_points, alpha_points[1:]):
        if a0 > 0.0 or a1 > 0.0:
            if out and out[-1][1] == x0:
                out[-1] = (out[-1][0], x1)
            else:
                out.append((x0, x1))
    return out


def share_in_tf_support(cell: dict, field: np.ndarray) -> float:
    """The share of voxels whose normalised value lies where the transfer
    function's opacity is not zero."""
    top = float(np.iinfo(field.dtype).max)
    spans = [(lo * top, hi * top) for lo, hi in tf_support(
        cell["config_file"]["transfer_function"]["alpha"])]
    return float(np.mean([sum(np.count_nonzero((p > lo) & (p < hi))
                              for lo, hi in spans) / p.size
                          for p in field]))


def window_checks(cell: dict, kept: dict) -> list:
    """`raw_dataset.window_checks` with the data's check for a transfer
    function that is no ramp: resident once at the file's dtype and size,
    no copy in a frame's step (a traced run), the share of the voxels in
    the transfer function's support."""
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
    lo, hi = conf["limits"]["share_in_tf_support"]
    share = share_in_tf_support(cell, field)
    out.append(("share_of_voxels_in_the_tf_support", share, f"{lo}..{hi}",
                lo <= share <= hi))
    return out


def operand_planes(cell: dict):
    """As how many matmul operands the program says it resamples a chunk
    of the configuration's dtype (None: it has no such rule)."""
    from scenery_insitu_tpu.ops import slicer

    rule = getattr(slicer, "operand_planes", None)
    return rule and rule(np.dtype(cell["config_file"]["shape"]["dtype"]))


def build_session(cell: dict, overrides, seed: int, sink=None, viewer=None,
                  fed=None):
    """`raw_dataset.build_session` for a program that resamples the wide
    chunk by its bytes; the timed session's sink also keeps the first
    steered frame: the first delivered frame whose eye is not the
    starting camera's, with the view matrix it was rendered from."""
    dtype = np.dtype(cell["config_file"]["shape"]["dtype"])
    if operand_planes(cell) != dtype.itemsize:
        raise harness.BenchFailure(
            f"no field source: this checkout's march does not resample a "
            f"{dtype} chunk by its {dtype.itemsize} byte planes (its rule "
            f"says {operand_planes(cell)}): the {8 * dtype.itemsize}-bit "
            f"configuration is not supported by it")
    from chipbench.traffic import eye_of_view

    base = np.asarray(cell["traffic_file"]["steering"]["base_eye"])
    steered = {}

    def keep_steered(index: int, payload: dict) -> None:
        if not steered and payload["frame"] > 0:
            view = np.asarray(payload["meta"].view)
            if np.abs(eye_of_view(view) - base).max() > 1e-4:
                steered.update(frame=payload["frame"], view=view,
                               vdi_color=payload["vdi_color"],
                               vdi_depth=payload["vdi_depth"])
        sink(index, payload)

    timed = sink is not None and fed is None
    sess = _BASE.build_session(cell, overrides, seed,
                               keep_steered if timed else sink, viewer, fed)
    sess.chipbench_steered = steered
    return sess


def keep(sess) -> dict:
    """`raw_dataset.keep`, and the steered frame (the object: it is
    filled later in the warm-up, once the viewer steers)."""
    return dict(_BASE.keep(sess), steered=sess.chipbench_steered)


def compare(cell: dict, kept: dict, ref: dict) -> list:
    """`raw_dataset.compare` (the resident bytes; frame 0 against the
    plain raycast), and the first steered frame against the plain raycast
    from ITS eye, held to the same floor."""
    from chipbench import reference
    from chipbench.traffic import eye_of_view

    out = _BASE.compare(cell, kept, ref)
    floor = cell["config_file"]["limits"]["raycast_psnr_floor_db"]
    name, got = "raycast_psnr_dB_steered_frame", kept.get("steered")
    if not got:
        return out + [(name, "not delivered", floor, False)]
    image = kept.get("steered_image")
    if image is None:
        image = reference.decode(got["vdi_color"], got["vdi_depth"])
    q = reference.psnr(raycast(cell, ref["field0"],
                               eye=eye_of_view(got["view"])), image)
    return out + [(name, q, floor, q >= floor)]


def rounded(cell: dict, seed: int, kept: dict) -> dict:
    """`raw_dataset.rounded`, with the raycast computed in bfloat16 where
    the decoded steered frame would stand too."""
    from chipbench.traffic import eye_of_view

    out = _BASE.rounded(cell, seed, kept)
    if kept.get("steered"):
        out["steered_image"] = raycast(
            cell, _BASE.plain_reference(cell, seed)["field0"], "bfloat16",
            eye_of_view(kept["steered"]["view"]))
    return out


# the accepted source's functions find these two by name in its module
_BASE.Widened, _BASE.raycast = Native, raycast

slab, generate, dataset_name = _BASE.slab, _BASE.generate, _BASE.dataset_name
write_file = _BASE.write_file
wait, plain_reference = _BASE.wait, _BASE.plain_reference
