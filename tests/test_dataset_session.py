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


# --------------------------------------------------------------- 16 bits
#
# ISSUE 49: a u16 field meets the resampling matmuls as its two byte
# planes (`ops/slicer.resample_wide`); the `beechnut` transfer function's
# tent 0.43-0.494 is 4,194 counts wide, 33 bf16 steps of 128.

BEECHNUT = [(0.0, 0.0), (0.43, 0.0), (0.457, 0.321), (0.494, 0.0),
            (1.0, 0.0)]


def low_byte_volume(dims=DIMS, seed=0) -> np.ndarray:
    """A u16 volume [z, y, x] whose structure lives in the LOW byte alone:
    0x7300 (0.449 of the range, inside Beechnut's tent) plus a smooth
    pattern of 0..255 in a ball, air at 0x1000 around it."""
    w, h, d = dims
    z, y, x = np.meshgrid(np.arange(d), np.arange(h), np.arange(w),
                          indexing="ij")
    # off centre and low in z: it reaches plane 0, the far end of the
    # march's last, partial chunk
    r = np.sqrt((x - 17) ** 2 + (y - 26) ** 2 + ((z - 5) * 1.5) ** 2)
    low = np.round(127.5 + 127.5 * np.sin(0.9 * x + 0.7 * y + 0.5 * z + seed))
    return np.where(r < 14, 0x7300 + low, 0x1000).astype(np.uint16)


def test_byte_planes_are_exact_in_bf16_and_recombine():
    """All 65,536 values of a u16: every byte plane survives a round trip
    through bfloat16 (the value itself does not), and 256 * hi + lo is
    the value."""
    from scenery_insitu_tpu.ops import slicer

    x = jnp.arange(65536, dtype=jnp.uint32).astype(jnp.uint16)
    hi, lo = slicer.byte_planes(x)
    f32, bf = jnp.float32, jnp.bfloat16
    for plane in (hi, lo):
        assert int(plane.max()) == 255 and int(plane.min()) == 0
        assert np.array_equal(plane.astype(bf).astype(f32),
                              plane.astype(f32))
    assert np.array_equal(hi.astype(f32) * 256.0 + lo.astype(f32),
                          x.astype(f32))
    assert not np.array_equal(x.astype(bf).astype(f32), x.astype(f32))
    assert slicer.operand_planes(np.uint16) == 2
    assert slicer.operand_planes(np.uint16, "f32") == 1
    assert [slicer.operand_planes(d) for d in (
        np.uint8, np.float32, jnp.bfloat16)] == [1, 1, 1]
    (only,) = slicer.byte_planes(jnp.arange(256, dtype=jnp.uint8))
    assert np.array_equal(only, np.arange(256))


def test_the_split_keeps_sixteen_bits_through_bf16_operands():
    """`resample_wide` with bfloat16 operands (what a TPU runs) against
    the contraction in float64 with exact weights, in counts of 65,535,
    on values inside Beechnut's tent with all their structure in the low
    byte: under 1 count rms, where ONE rounded bf16 operand (what an f32
    operand is to the MXU at the default precision) reads over 20 with
    the same renormalised weights, and the split without the second
    contraction's two terms or without the renormalisation over 15."""
    from scenery_insitu_tpu.ops import slicer

    rng = np.random.default_rng(49)
    c, ny, nx, nj, ni = 4, 48, 40, 64, 56
    chunk = (0x7300 + rng.integers(0, 256, (c, ny, nx))).astype(np.uint16)
    sk = 0.8 + 0.01 * jnp.arange(c, dtype=jnp.float32)
    pos_u = 0.013 + jnp.linspace(-1, 1, ni)[None] * sk[:, None]
    pos_v = -0.021 + jnp.linspace(-1, 1, nj)[None] * sk[:, None]
    wu = slicer._interp_matrix(pos_u, -1.0, 2.0 / nx, nx)
    wv = slicer._interp_matrix(pos_v, -1.0, 2.0 / ny, ny)
    want = np.einsum("cjy,cyx,cix->cji", np.asarray(wv, np.float64),
                     chunk.astype(np.float64), np.asarray(wu, np.float64))
    inside = np.asarray((wv.sum(-1) > 0)[:, :, None]
                        & (wu.sum(-1) > 0)[:, None, :])
    rms = lambda got: float(np.sqrt(np.mean(
        (np.asarray(got, np.float64) - want)[inside] ** 2)))
    bf, f32 = jnp.bfloat16, jnp.float32
    assert rms(slicer.resample_wide(wv, jnp.asarray(chunk), wu)) < 1.0
    wvb, wub = wv.astype(bf), wu.astype(bf)
    norm = (slicer.rounded_row_sums(wv)[:, :, None]
            * slicer.rounded_row_sums(wu)[:, None, :])
    ein = lambda a, b, s: jnp.einsum(s, a, b, preferred_element_type=f32)
    rounded = ein(ein(wvb, jnp.asarray(chunk).astype(bf), "cjy,cyx->cjx"
                      ).astype(bf), wub, "cjx,cix->cji")
    assert rms(rounded / jnp.maximum(norm, 1e-6)) > 20.0
    hi, lo = slicer.byte_planes(jnp.asarray(chunk))
    t = (ein(wvb, hi.astype(bf), "cjy,cyx->cjx") * 256.0
         + ein(wvb, lo.astype(bf), "cjy,cyx->cjx"))
    one_term = ein(t.astype(bf), wub, "cjx,cix->cji")
    assert rms(one_term / jnp.maximum(norm, 1e-6)) > 15.0
    t1 = t.astype(bf)
    two_terms = (ein(t1, wub, "cjx,cix->cji")
                 + ein((t - t1.astype(f32)).astype(bf), wub, "cjx,cix->cji"))
    assert rms(two_terms) > 15.0            # weights that sum to 1 +- 2^-9


def beechnut_frame(home, dims):
    """Frame 0 of a session on `<home>/beechnut.raw` (a later override
    of a key wins)."""
    cfg = FrameworkConfig().with_overrides(
        *overrides(home, K, "runtime.dataset=beechnut"))
    sim = DatasetVolumeAdapter(cfg, dims_xyz=dims, dtype=np.uint16)
    return frames(cfg, sim)[1][0]


def beechnut_raycast(volume_f32, dims):
    w, h, _ = dims
    return reference_raycast.render(
        jnp.asarray(volume_f32), EYE, int(np.ceil(w * 1.25 / 8) * 8),
        int(np.ceil(h * 1.25 / 8) * 8), BEECHNUT)


@pytest.mark.parametrize("depth", [27, 26, 32])
def test_sixteen_bits_in_the_low_byte_reach_the_frame(depth, data_dir):
    """A volume whose structure is in the low byte alone, under the
    `beechnut` transfer function (a tent 33 bf16 steps wide), through the
    session: frame 0 against `reference_raycast` of the widened volume
    reads over the 100 dB floor; the raycast of the same volume rounded
    to bfloat16 (8 of its 16 bits: what one matmul operand keeps) reads
    at least 10 dB UNDER it: the fault the byte planes are there for.
    Depth 26 = 16 + 10 is Beechnut's remainder (1546 = 96 x 16 + 10)
    through the split, 27 Kingsnake's 11, 32 none."""
    dims = DIMS[:2] + (depth,)
    raw = low_byte_volume(dims)
    got = beechnut_frame(data_dir(raw, "beechnut"), dims)
    assert got["vdi_color"].shape == (K, 4, 64, 56)
    have = image(got)
    widened = raw.astype(np.float32) / 65535
    want = beechnut_raycast(widened, dims)
    assert want[3].max() > 0.3              # the tent was hit
    assert reference.psnr(want, have) > RAYCAST_FLOOR_DB
    rounded = np.asarray(jnp.asarray(widened).astype(jnp.bfloat16)
                         .astype(jnp.float32))
    assert reference.psnr(beechnut_raycast(rounded, dims),
                          have) < RAYCAST_FLOOR_DB - 10
    dropped = raw.copy()
    dropped[0] = 0x1000                     # marched last from this eye
    if depth % 16:                          # the remainder chunk counts
        assert reference.psnr(beechnut_raycast(
            dropped.astype(np.float32) / 65535, dims),
            have) < RAYCAST_FLOOR_DB - 10


def _dots(jaxpr, out=None):
    """(lhs dtype, rhs dtype, precision) of every BATCHED dot_general
    in a jaxpr, nested ones included: the resampling contractions (batch
    = the chunk's slices) and the XLA shading's colour sums; the camera's
    small matrix products have no batch dimension."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "dot_general"
                and eqn.params["dimension_numbers"][1][0]):
            out.append((eqn.invars[0].aval.dtype.name,
                        eqn.invars[1].aval.dtype.name,
                        eqn.params["precision"]))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _dots(inner, out)
    return out


@pytest.mark.parametrize("dtype,planes,mm", [
    (np.uint16, 2, "bf16"), (np.uint8, 1, "bf16"), (np.uint16, 1, "f32")])
def test_the_step_program_by_operand(dtype, planes, mm, data_dir,
                                     monkeypatch):
    """The frame's step as a TPU builds its march (bfloat16 matmul
    operands; `make_spec` gives f32 off the TPU, so the spec's operand
    dtype is steered here, in the test): a u16 field's step holds no
    matmul with an operand wider than bfloat16 and none above the default
    precision, twice the u8 step's dots; the u8 step's two dots are
    bf16 x bf16 for the first contraction and f32 x bf16 for the second
    as they were (the intermediate of jnp.einsum's three-operand form);
    neither writes an array as large as the field, and a recorded run
    counts `march_operand_planes` 2 / 1 a frame. Where f32 operands are
    asked for (`slicer.matmul_dtype=f32`, valid on a TPU too) the u16
    chunk is ONE f32 operand and every contraction asks for
    Precision.HIGHEST: at the default a TPU would round it to bf16."""
    import dataclasses

    import jax
    from scenery_insitu_tpu.obs.profiler import hlo_large_writes
    from scenery_insitu_tpu.ops import slicer

    from scenery_insitu_tpu.runtime import steps

    real = slicer.make_spec
    monkeypatch.setattr(slicer, "make_spec", lambda *a, **k:
                        dataclasses.replace(real(*a, **k),
                                            matmul_dtype=mm))
    seen, scoped = [], steps.scoped_step

    def spy(fn, rec):           # the jitted step and its first call's avals
        inner = scoped(fn, rec)

        def call(*args):
            seen.append((fn, jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)))
            return inner(*args)
        call.lower = fn.lower
        return call
    monkeypatch.setattr(steps, "scoped_step", spy)
    raw = (low_byte_volume() if dtype == np.uint16
           else seeded(np.uint8))
    name = "beechnut" if dtype == np.uint16 else "kingsnake"
    cfg = FrameworkConfig().with_overrides(
        *overrides(data_dir(raw, name), K, f"runtime.dataset={name}",
                   "obs.enabled=true"))
    sess, got = frames(cfg, DatasetVolumeAdapter(cfg, dims_xyz=DIMS,
                                                 dtype=dtype), 3)
    assert sess.obs.counters["march_operand_planes"] == 3 * planes
    assert sum(e["type"] == "span" and e["name"] == "dispatch"
               for e in sess.obs.events) == 3      # one a step call
    assert sess.obs.counters["volume_copies_per_frame"] == 0
    assert got[0]["vdi_color"][:, 3].max() > 0.0
    step, args = seen[0]
    dots = _dots(jax.make_jaxpr(step)(*args).jaxpr)
    march = sorted(d[:2] for d in dots if d[2] is None)
    if mm == "f32":
        assert dots and not march
        assert {d[:2] for d in dots} == {("float32", "float32")}
        return
    assert march and len({d[:2] for d in dots if d[2] is not None}) <= 1
    # per output row block where the occupancy tiles gate the march
    per_block = len(march) // (2 * planes)
    if planes == 2:
        assert march == [("bfloat16", "bfloat16")] * 4 * per_block
    else:
        assert march == ([("bfloat16", "bfloat16")] * per_block
                         + [("float32", "bfloat16")] * per_block)
    text = step.lower(*args).compile().as_text()
    assert hlo_large_writes(text, DIMS[::-1]) == []


@pytest.mark.parametrize("eye_z", [3.0, -3.0])
def test_the_occupancy_ranges_of_a_u16_field(eye_z):
    """`ops/occupancy.volume_ranges` reduces in the storage dtype and
    scales: per (chunk x tile) of a u16 field with a remainder chunk of
    10 planes, numpy's min / max of the same planes and rows over 65,535,
    in march order for either sign."""
    from scenery_insitu_tpu.config import SliceMarchConfig
    from scenery_insitu_tpu.core.camera import Camera
    from scenery_insitu_tpu.ops import occupancy, slicer

    dims = DIMS[:2] + (26,)
    raw = low_byte_volume(dims)
    raw[3, 5, 7], raw[20, 40, 2] = 65535, 0
    vol = V.Volume.centered(jnp.asarray(raw), extent=2.0)
    cam = Camera.create((0.0, 0.6, eye_z), fov_y_deg=50.0, near=0.3,
                        far=20.0)
    spec = slicer.make_spec(cam, raw.shape,
                            SliceMarchConfig(occupancy_vtiles=4))
    assert (spec.axis, spec.sign) == (2, -1 if eye_z > 0 else 1)
    lo, hi = occupancy.volume_ranges(vol, spec)
    assert lo.shape == hi.shape == (2, 4) and lo.dtype == jnp.float32
    marched = raw[::-1] if spec.sign < 0 else raw
    for ci, planes in enumerate((marched[:16], marched[16:])):
        for t, (r0, r1) in enumerate(occupancy._tile_bands(48, 4)):
            cell = planes[:, r0:r1]
            assert float(lo[ci, t]) == np.float32(cell.min()) * np.float32(
                1 / 65535)
            assert float(hi[ci, t]) == np.float32(cell.max()) * np.float32(
                1 / 65535)
    assert float(hi.max()) == 1.0 and float(lo.min()) == 0.0


def test_the_tent_through_the_fold_kernels_shading():
    """`pallas_seg._shade_plane` (the fold kernel's transfer function in
    knot form, its knots as immediates) on Beechnut's five-point tent,
    which is not monotone, against `TransferFunction.__call__` and
    `adjust_opacity`: every 16-bit value across the tent and beside it,
    a dead sample, to f32 rounding. (The kernel itself on a u16 tent
    volume, interpret mode, against the XLA fold: `tests/test_seg_fold.py`
    case `u16_k20_depth26_tent`.)"""
    from scenery_insitu_tpu.core.transfer import for_dataset
    from scenery_insitu_tpu.ops import pallas_seg as psg
    from scenery_insitu_tpu.ops.sampling import adjust_opacity

    tf = for_dataset("beechnut")
    tfc = psg._tf_consts(tf)
    counts = np.arange(0.40 * 65535, 0.52 * 65535, dtype=np.float32)
    val = jnp.asarray(np.concatenate([counts / 65535, [0.0, 1.0, -1.0]])
                      .astype(np.float32).reshape(1, -1))
    ratio = jnp.full(val.shape, 1.7, jnp.float32)
    got = np.asarray(psg._shade_plane(val, ratio, tfc))
    rgb, alpha = tf(jnp.clip(val, 0.0, 1.0))
    alpha = adjust_opacity(jnp.where(val < -0.5, 0.0, alpha), ratio)
    want = np.concatenate([np.moveaxis(np.asarray(rgb), -1, 0)
                           * np.asarray(alpha)[None],
                           np.asarray(alpha)[None]])
    assert got.shape == want.shape == (4, 1, val.shape[1])
    assert got[3].max() > 0.45 and got[3, 0, -1] == 0.0   # 1-(1-.321)^1.7
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # the peak sits where the table says, and both flanks fall to zero
    peak = counts[np.argmax(got[3, 0, :counts.size])] / 65535
    assert abs(peak - 0.457) < 1e-4
    assert got[3, 0, 0] == 0.0 and got[3, 0, counts.size - 1] == 0.0
