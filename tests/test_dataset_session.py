"""The file-dataset path (ISSUE 44): a raw volume file through
`InSituSession` at its native dtype — the loader in z-parts, the
`DatasetVolumeAdapter`, the integer march (exact operand, 1/max on the
accumulator), a depth that is no chunk multiple, K = 20 — against the
benchmark's independent raycaster (`chipbench/reference_raycast.py`) and
against the same session fed the widened f32 volume. Small sizes, CPU."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import reference, reference_raycast  # noqa: E402
from scenery_insitu_tpu import obs  # noqa: E402
from scenery_insitu_tpu.config import FrameworkConfig  # noqa: E402
from scenery_insitu_tpu.core import volume as V  # noqa: E402
from scenery_insitu_tpu.runtime.session import (  # noqa: E402
    DatasetVolumeAdapter, InSituSession)

DIMS = (40, 48, 27)             # x, y, z: non-cubic, 27 = 16 + 11
ALPHA = [(0.0, 0.0), (0.43, 0.0), (0.5, 0.005)]     # the kingsnake table's
EYE = (0.0, 0.6, 3.0)           # the session's default camera
K = 20


def seeded(dtype, seed=0) -> np.ndarray:
    """A CT-like volume [z, y, x] of `dtype`: a dense ball off centre in
    noisy air under the transfer function's knee; it reaches plane z = 0,
    the far end of the march's last, partial chunk."""
    w, h, d = DIMS
    rng = np.random.default_rng(seed)
    z, y, x = np.meshgrid(np.arange(d), np.arange(h), np.arange(w),
                          indexing="ij")
    r = np.sqrt((x - 17) ** 2 + (y - 26) ** 2 + ((z - 6) * 1.5) ** 2)
    v = np.where(r < 11, 0.55 + 0.35 * np.cos(r), 0.05 + 0.3 * rng.random(
        (d, h, w)))
    return np.round(np.clip(v, 0, 1) * np.iinfo(dtype).max).astype(dtype)


@pytest.fixture()
def data_dir(tmp_path):
    def write(raw, name="kingsnake"):
        raw.tofile(tmp_path / f"{name}.raw")
        return str(tmp_path)
    return write


def overrides(data_dir, k=K, *more):
    return ["slicer.engine=mxu", "vdi.adaptive_mode=temporal",
            f"vdi.max_supersegments={k}",
            f"composite.max_output_supersegments={k}",
            "runtime.dataset=kingsnake", f"runtime.data_dir={data_dir}",
            "mesh.num_devices=1", *more]


class Fed:
    """A static field behind the facade a session takes as `sim=`."""
    kind, static = "dataset", True

    def __init__(self, field):
        self.field = jnp.asarray(field)

    def advance(self, n):
        pass


def frames(cfg, sim, n=1):
    got = {}
    sess = InSituSession(cfg, sim=sim, sinks=[
        lambda i, p: got.__setitem__(p["frame"], p)])
    sess.run(n)
    return sess, got


def image(payload):
    return reference.decode(payload["vdi_color"], payload["vdi_depth"])


def raycast(raw, top=None):
    widened = raw.astype(np.float32) / (top or np.iinfo(raw.dtype).max)
    return reference_raycast.render(jnp.asarray(widened), EYE, 56, 64,
                                    ALPHA)


@pytest.mark.parametrize("dtype", [np.uint8, np.uint16])
def test_the_loader_in_parts_equals_fromfile_whole(dtype, data_dir):
    raw = seeded(dtype)
    home = data_dir(raw)
    took = {}
    field = V.load_raw_parts(os.path.join(home, "kingsnake.raw"), DIMS,
                             dtype, parts=5, timings=took)
    whole = np.fromfile(os.path.join(home, "kingsnake.raw"), dtype)
    assert field.dtype == dtype and field.shape == DIMS[::-1]
    assert np.array_equal(np.asarray(field).ravel(), whole)
    assert took["parts"] == 5 and took["read"] > 0 and took["put"] > 0


def test_a_file_of_another_size_is_an_error(data_dir):
    home = data_dir(seeded(np.uint8))
    with pytest.raises(ValueError, match="bytes"):
        V.load_raw_parts(os.path.join(home, "kingsnake.raw"), DIMS,
                         np.uint16)


def test_the_dtype_table_names_every_dataset():
    assert set(V.DATASET_DTYPES) == set(V.DATASET_DIMS_XYZ)
    assert np.dtype(V.DATASET_DTYPES["kingsnake"]) == np.uint8
    assert np.dtype(V.DATASET_DTYPES["beechnut"]) == np.uint16
    assert V.value_scale(np.uint8) == 1 / 255
    assert V.value_scale(np.uint16) == 1 / 65535
    assert V.value_scale(np.float32) == 1.0


def test_the_session_builds_the_adapter_from_the_config(data_dir,
                                                        monkeypatch):
    """`runtime.dataset` names a table entry: no `sim=`, the session loads
    the file itself, at the table's dims and dtype."""
    monkeypatch.setitem(V.DATASET_DIMS_XYZ, "kingsnake", DIMS)
    raw = seeded(np.uint8)
    cfg = FrameworkConfig().with_overrides(
        *overrides(data_dir(raw)), "obs.enabled=true")
    obs.clear_ledger()      # whatever this worker's earlier tests left
    sess, got = frames(cfg, None, 3)
    assert isinstance(sess.sim, DatasetVolumeAdapter)
    field = sess.sim.field
    assert field.dtype == np.uint8
    assert np.asarray(field).tobytes() == raw.tobytes()
    assert sess.sim.field is field          # resident, not loaded again
    load = [e for e in sess.obs.events if e.get("name") == "dataset.load"]
    assert len(load) == 1
    assert load[0]["attrs"]["bytes"] == raw.nbytes
    assert load[0]["attrs"]["dtype"] == "uint8"
    assert load[0]["attrs"]["parts"] == 8
    assert sess.obs.counters["volume_resident_bytes"] == raw.nbytes
    # the occupancy ranges of a field that never changes are computed at
    # the regime's entry: only the threshold seeder's program sweeps the
    # volume for a pyramid, the step's takes the kept ranges (a count of
    # programs traced, not of frames); a field that may change sweeps in
    # the step too
    assert sess.obs.counters["occupancy_pyramid_builds"] == 1
    moving = Fed(raw)
    moving.static = False
    sess2, got2 = frames(cfg, moving, 1)
    assert sess2.obs.counters["occupancy_pyramid_builds"] == 2
    assert np.array_equal(got2[0]["vdi_color"], got[0]["vdi_color"])
    assert sorted(got) == [0, 1, 2]
    assert got[0]["vdi_color"].shape == (K, 4, 64, 56)
    assert obs.ledger() == []


RAYCAST_FLOOR_DB = 100.0


@pytest.mark.parametrize("dtype", [np.uint8, np.uint16])
def test_the_session_agrees_with_the_plain_raycast(dtype, data_dir):
    """Frame 0 decoded against `reference_raycast`: both f32 on the CPU
    (the march's matmuls too, off the TPU), so they differ by the order
    of the additions only and read 113-119 dB. The floor of 100 dB lies
    15 dB over what the two faults it is there for read: the volume
    normalised by 1/256 (88 dB; of u16 a 1/65536 is within rounding and
    is not tried) and the far plane of the last, partial chunk dropped
    (85 dB). K slots of the payload are counted by its shape."""
    raw = seeded(dtype)
    cfg = FrameworkConfig().with_overrides(*overrides(data_dir(raw)))
    sim = DatasetVolumeAdapter(cfg, dims_xyz=DIMS, dtype=dtype)
    _, got = frames(cfg, sim)
    assert got[0]["vdi_color"].shape == (K, 4, 64, 56)
    assert got[0]["vdi_depth"].shape == (K, 2, 64, 56)
    have = image(got[0])
    want = raycast(raw)
    assert want[3].max() > 0.03             # something was rendered
    assert reference.psnr(want, have) > RAYCAST_FLOOR_DB
    dropped = raw.copy()
    dropped[0] = 0                          # marched last from this eye
    assert reference.psnr(raycast(dropped), have) < RAYCAST_FLOOR_DB - 10
    if dtype == np.uint8:
        assert reference.psnr(raycast(raw, 256.0),
                              have) < RAYCAST_FLOOR_DB - 10


@pytest.mark.parametrize("dtype", [np.uint8, np.uint16])
def test_resident_integers_render_as_the_widened_volume(dtype, data_dir):
    """The u8 / u16-resident render against the same session fed the
    volume widened as `load_raw` widens: equal up to the rounding of one
    multiply (the scale on the accumulator) per sample, 1e-6 in colour
    and bit for bit in depth; a / 256 normalisation or a K cut to 16
    are orders of magnitude outside."""
    raw = seeded(dtype)
    home = data_dir(raw)
    top = np.iinfo(dtype).max
    cfg = FrameworkConfig().with_overrides(*overrides(home))
    _, got = frames(cfg, DatasetVolumeAdapter(cfg, dims_xyz=DIMS,
                                              dtype=dtype), 3)
    _, ref = frames(cfg, Fed(raw.astype(np.float32) / top), 3)
    finite = lambda d: np.nan_to_num(d, posinf=0.0)
    for f in range(3):
        assert np.abs(got[f]["vdi_color"] - ref[f]["vdi_color"]).max() < 2e-6
        assert np.array_equal(finite(got[f]["vdi_depth"]),
                              finite(ref[f]["vdi_depth"]))
    if dtype == np.uint8:       # (of u16 a / 65536 is within rounding)
        _, off = frames(cfg, Fed(raw.astype(np.float32) / (top + 1)))
        assert np.abs(got[0]["vdi_color"]
                      - off[0]["vdi_color"]).max() > 1e-4
    cfg16 = FrameworkConfig().with_overrides(*overrides(home, 16))
    _, cut = frames(cfg16, DatasetVolumeAdapter(cfg16, dims_xyz=DIMS,
                                                dtype=dtype))
    assert cut[0]["vdi_color"].shape[0] == 16 != K


def test_no_frame_copies_the_volume(data_dir):
    """`volume_copies_per_frame`: instructions of the step's compiled
    program that write an array as large as the field. None for the z
    march of either sign (the field is read where it lives, whatever the
    depth); a march along x has to transpose it, and the counter sees
    that copy on every frame."""
    from scenery_insitu_tpu.core.camera import Camera

    raw = seeded(np.uint8)
    cfg = FrameworkConfig().with_overrides(
        *overrides(data_dir(raw)), "obs.enabled=true")
    for eye, copies in (((0.0, 0.6, 3.0), 0), ((0.0, 0.6, -3.0), 0),
                        ((3.0, 0.6, 0.2), 1)):
        sess = InSituSession(
            cfg, sim=DatasetVolumeAdapter(cfg, dims_xyz=DIMS,
                                          dtype=np.uint8),
            camera=Camera.create(eye, fov_y_deg=50.0, near=0.3, far=20.0))
        sess.run(4)
        per_frame = sess.obs.counters["volume_copies_per_frame"] / 4
        assert (per_frame >= 1) if copies else (per_frame == 0), (eye,
                                                                  per_frame)
        assert np.asarray(sess.sim.field).tobytes() == raw.tobytes()


def test_the_gather_engine_samples_resident_integers_normalised(data_dir):
    """`slicer.engine=gather` (what `auto` resolves to off the TPU) reads
    the same resident u8 field through `ops/sampling.sample_trilinear`,
    which scales what it sampled: the frame equals the widened volume's
    up to that one multiply."""
    raw = seeded(np.uint8)
    cfg = FrameworkConfig().with_overrides(
        "slicer.engine=gather", "vdi.max_supersegments=8",
        "composite.max_output_supersegments=8", "render.width=48",
        "render.height=40", "render.max_steps=48",
        "runtime.dataset=kingsnake", f"runtime.data_dir={data_dir(raw)}",
        "mesh.num_devices=1")
    _, got = frames(cfg, DatasetVolumeAdapter(cfg, dims_xyz=DIMS,
                                              dtype=np.uint8))
    _, ref = frames(cfg, Fed(raw.astype(np.float32) / 255))
    assert got[0]["vdi_color"][:, 3].max() > 0.01
    assert np.abs(got[0]["vdi_color"] - ref[0]["vdi_color"]).max() < 2e-6
