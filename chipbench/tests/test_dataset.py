"""The raw-dataset configuration (PR 44) at a rehearsal size on the CPU:
the whole chain of its cell (48 x 40 x 27 u8, a depth that is no chunk
multiple, K = 20), the control, the shape arithmetic against hand numbers,
the three readers on canned sources, the plain raycaster's grid against
the program's own, and the files against the program's tables."""

import os

import numpy as np
import pytest

from chipbench import (arith, arith_dataset, harness, reference,
                       reference_raycast, rehearse, scopes, xplane)

HOME = os.path.join(harness.HERE, "rehearsal", "dataset")
FIX = harness.load_json(harness.HERE, "fixtures", "scopes_small.json")
SEED = 4400000077


def cell() -> dict:
    c = harness.find_files(
        {"name": "rehearsal-tiny-dataset", "config": "tiny-dataset",
         "traffic": "dataset-steer"}, home=HOME)
    return dict(c, chips=c["config_file"]["chips"])


def real() -> dict:
    return harness.load_cell("kingsnake-u8-view")


def readers() -> dict:
    names = ("dataset_load_s", "volume_resident_MB", "march_hbm_share")
    return {m.NAME: m for m in harness.load_layers() if m.NAME in names}


@pytest.fixture(scope="module")
def rehearsed():
    """One traced rehearsal of the cell, its checks by name."""
    res = rehearse.rehearse(cell(), SEED, 1.0, True)
    return res, {n: (v, ok) for n, v, _, ok in res["checks"]}


def test_the_rehearsal_cell_is_correct(rehearsed):
    res, checks = rehearsed
    assert res["correct"], [n for n, (_, ok) in checks.items() if not ok]
    assert res["failed"] == 0
    # the guarantees of the configuration, each by its own check
    assert checks["vdi_bytes_per_frame"][0] == 20 * 24 * 56 * 64
    assert checks["volume_resident_bytes"][0] == 27 * 40 * 48
    assert checks["field_dtype"][0] == "uint8"
    assert checks["field_max_abs_diff"][0] == 0
    assert checks["volume_copies_per_frame"][0] == 0      # a traced run
    assert checks["fallback_ledger_rows"][0] == 0
    assert checks["fallback_ledger_rows_reference"][0] == 0
    assert checks["compile_requests_in_window"][0] == 0
    assert checks["raycast_psnr_dB_frame0"][0] > 100.0
    assert checks["decoded_psnr_dB_window_frame"][0] > 100.0
    assert checks["steering_answers_in_window"][0] >= 1


def test_every_all_cell_reader_of_the_host_reads_there(rehearsed):
    """No `null` among the readers that take no device plane (a CPU
    rehearsal has none): every `all`-cell span and counter reader finds
    its source in a session that has no simulation."""
    res, _ = rehearsed
    want = {m.NAME for m in harness.load_layers()
            if m.CELLS == "all" and m.SOURCE != "device_trace"}
    assert want <= set(res["per_layer"]), want - set(res["per_layer"])


def test_the_control_is_not_correct():
    """The lowest bit dropped where the resident bytes would stand, the
    raycast in bfloat16 where the decoded frame would: the field's limit
    refuses it, and the image falls tens of dB."""
    from chipbench import control

    res = control.read(cell(), SEED + 1, 1.0, "rounded", on_chip=False)
    checks = {n: (v, ok) for n, v, _, ok in res["checks"]}
    assert not res["correct"]
    assert checks["field_max_abs_diff"] == (1, False)
    # 72 dB where the program's frame reads 108-113: the floor of 90
    # stands between the two
    assert checks["raycast_psnr_dB_frame0"][0] < 80.0
    assert not checks["raycast_psnr_dB_frame0"][1]
    assert checks["frames_delivered_once_in_order"][1]


def test_shape_arithmetic_against_hand_numbers():
    shape = real()["config_file"]["shape"]
    assert arith.intermediate_grid(shape) == (1280, 1280)
    assert arith.vdi_bytes_per_frame(shape) == 786_432_000
    assert arith_dataset.volume_bytes(shape) == 833_617_920
    assert arith_dataset.volume_bytes(shape, "float32") == 3_334_471_680
    assert arith_dataset.march_floor_bytes_per_frame(shape) == \
        1_620_049_920
    assert arith.march_dense_flops_per_frame(shape) == pytest.approx(
        795 * (2 * 1280 * 1024 * 1024 + 2 * 1280 * 1024 * 1280))


def test_the_files_say_what_the_program_tables_say():
    """The configuration's grid, dtype and transfer function are the
    program's table entries for the dataset its overrides name; the
    traffic file is `insitu10-steer`'s viewer and window, no sim key."""
    import jax.numpy as jnp

    from chipbench.sources import raw_dataset
    from scenery_insitu_tpu.core import transfer, volume

    conf = real()["config_file"]
    name = raw_dataset.dataset_name(real())
    assert name == "kingsnake"
    assert tuple(reversed(conf["shape"]["grid"])) == \
        volume.DATASET_DIMS_XYZ[name]
    assert np.dtype(conf["shape"]["dtype"]) == volume.DATASET_DTYPES[name]
    assert conf["reduced"] == [] and conf["chips"] == 1
    assert conf["control_overrides"] == []
    pts = conf["transfer_function"]["alpha"]
    x = np.linspace(0.0, 1.0, 101, dtype=np.float32)
    rgb, alpha = transfer.for_dataset(name)(jnp.asarray(x))
    assert np.allclose(alpha, np.interp(x, *zip(*pts)), atol=1e-7)
    assert np.allclose(rgb, x[:, None], atol=1e-6)      # grays
    ours = harness.load_json(harness.HERE, "traffic", "dataset-steer.json")
    theirs = harness.load_json(harness.HERE, "traffic",
                               "insitu10-steer.json")
    for key in theirs:
        if key not in ("name", "what", "field", "field_perturbation",
                       "checked"):
            assert ours[key] == theirs[key], key
    assert "overrides" not in ours and "pre_evolve_steps" not in ours


def test_the_raycasters_grid_is_the_marchs_own():
    """`reference_raycast.axis_grid`, written out from the grid's
    definition, against the program's virtual camera at the cell's real
    dims: the same pixel positions, reference plane and ladder."""
    import jax.numpy as jnp

    from scenery_insitu_tpu.core.camera import Camera
    from scenery_insitu_tpu.core.volume import Volume
    from scenery_insitu_tpu.ops import slicer

    dims = (795, 1024, 1024)
    for eye in ((0.0, 0.6, 3.0), (0.21, 0.5, -2.9)):
        g = reference_raycast.axis_grid(eye, dims, 1280, 1280)
        origin, vox = reference_raycast.placement(dims)
        cam = Camera.create(eye, fov_y_deg=50.0, near=0.3, far=20.0)
        spec = slicer.make_spec(cam, dims)
        vol = Volume(jnp.zeros((1, 1, 1), jnp.uint8), jnp.asarray(
            origin, jnp.float32), jnp.full((3,), vox, jnp.float32))
        box_max = jnp.asarray(origin + np.array(dims[::-1]) * vox,
                              jnp.float32)
        axcam = slicer.make_axis_camera(vol, cam, spec, vol.origin, box_max)
        assert (spec.axis, spec.sign) == (2, int(g["sign"]))
        assert np.allclose(axcam.u_grid, g["u"], atol=2e-6)
        assert np.allclose(axcam.v_grid, g["v"], atol=2e-6)
        assert float(axcam.zp) == pytest.approx(g["zp"], abs=1e-6)
        assert float(axcam.w0) == pytest.approx(g["w0"], abs=1e-6)
    with pytest.raises(ValueError, match="along z"):
        reference_raycast.axis_grid((3.0, 0.6, 0.1), dims, 1280, 1280)


def test_the_raycast_of_a_uniform_slab_is_the_closed_form():
    """A volume of one value v: every ray through it accumulates
    1 - (1 - a)^(path / voxel) with a = alpha(v), whatever the planes;
    the centre pixel's path is the depth seen along its ray."""
    import jax.numpy as jnp

    d, h, w = 12, 64, 64
    img = reference_raycast.render(
        jnp.full((d, h, w), 0.8, jnp.float32), (0.0, 0.0, 3.0), 80, 80,
        [(0.0, 0.0), (0.5, 0.0), (1.0, 0.1)])
    a = 0.06                                    # alpha(0.8)
    centre = img[:, 39:41, 39:41].mean(axis=(1, 2))
    assert centre[3] == pytest.approx(1 - (1 - a) ** d, rel=1e-3)
    assert centre[0] == pytest.approx(0.8 * centre[3], rel=1e-5)
    assert img[3, 0, 0] == 0.0                  # the margin sees nothing


def test_march_hbm_share_on_the_fixture(monkeypatch):
    """march 20 ms + fold 15 ms a frame on the fixture; the floor of the
    cell's shape, 1,620,049,920 B, over 35 ms and 819 GB/s."""
    monkeypatch.setattr(scopes, "table", lambda: (FIX["hlo_scopes"],
                                                  FIX["hlo_inherited"]))
    ctx = lambda: {
        "trace": xplane.Trace(FIX["events"]), "spans": FIX["spans"],
        "frames": FIX["frames"],
        "config": {"programs": real()["config_file"]["programs"]},
        "shape": real()["config_file"]["shape"],
        "peaks": arith.peaks_for("TPU v5 lite")}
    got = readers()["march_hbm_share"].read(ctx())
    assert got == pytest.approx(1_620_049_920 / 0.035 / 819e9 * 100)
    assert 0 < got < 100
    monkeypatch.setattr(scopes, "table", lambda: ({}, {}))
    assert readers()["march_hbm_share"].read(ctx()) is None


def test_the_recorders_readers_on_canned_sources(capsys):
    """`dataset_load_s` and `volume_resident_MB` read the program's
    recorder; where the program has no such span or counter (the parent)
    they give nothing and say so."""
    from scenery_insitu_tpu import obs

    rec = obs.Recorder(enabled=True)
    was = obs.set_recorder(rec)
    try:
        for name in ("dataset_load_s", "volume_resident_MB"):
            assert readers()[name].read({}) is None
        assert capsys.readouterr().err.count("MISSING SOURCE") == 2
        with rec.span("dataset.load", frame=0) as span:
            span.note(bytes=833617920, dtype="uint8", parts=8,
                      read_s=0.5, put_s=0.25)
        rec.count("volume_resident_bytes", 833617920)
        rec.count("volume_copies_per_frame", 0)
        load = [e for e in rec.events if e["name"] == "dataset.load"][0]
        assert readers()["dataset_load_s"].read({}) == load["dur"]
        assert readers()["volume_resident_MB"].read({}) == 833.61792
        err = capsys.readouterr().err
        assert "read 0.500 s, put 0.250 s" in err
        assert "volume_copies_per_frame (whole run): 0" in err
    finally:
        obs.set_recorder(was)


def test_a_program_without_the_adapter_fails_at_once(monkeypatch):
    """The parent commit: the source says `no field source` before it
    makes any data."""
    from chipbench.sources import raw_dataset
    from scenery_insitu_tpu.runtime import session

    monkeypatch.delattr(session, "DatasetVolumeAdapter")
    monkeypatch.setattr(raw_dataset, "write_file", lambda *a: 1 / 0)
    with pytest.raises(harness.BenchFailure, match="no field source"):
        raw_dataset.build_session(cell(), harness.overrides_of(cell()), 1)


def test_the_data_are_what_the_configuration_says():
    """The seeded volume at the rehearsal size: the same geometry for two
    seeds (the same voxels over the knee up to the skin's grain), other
    noise; air under the knee, tissue over it."""
    from chipbench.sources import raw_dataset

    c = cell()
    a, b = (raw_dataset.plain_reference(c, s)["field0"] for s in (1, 2))
    assert a.shape == (27, 40, 48) and a.dtype == np.uint8
    assert not np.array_equal(a, b)
    knee = 0.43 * 255
    assert np.mean((a > knee) != (b > knee)) < 0.02
    lo, hi = c["config_file"]["limits"]["share_over_knee"]
    assert lo <= raw_dataset.share_over_knee(c, a) <= hi
    noise = c["traffic_file"]["field_noise"]
    assert a.min() >= int(noise["air_low"] * 255) - 1
    assert (a[:, :3, :3] <= noise["air_high"] * 255 + 1).all()  # air only
    assert a.max() >= 0.85 * 255                # the core
