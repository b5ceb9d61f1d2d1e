"""The 16-bit raw-dataset configuration (PR 49) at a rehearsal size on the
CPU: the whole chain of its cell (48 x 40 x 26 u16, a depth of 16 + 10 as
Beechnut's 96 x 16 + 10, K = 20, the `beechnut` tent), the control, the
shape arithmetic against hand numbers, the four readers, the source's
differences from `raw_dataset`, and the files against the program's
tables."""

import os

import numpy as np
import pytest

from chipbench import (arith, arith_dataset, arith_dataset16, harness,
                       rehearse, scopes, xplane)

HOME = os.path.join(harness.HERE, "rehearsal", "dataset")
FIX = harness.load_json(harness.HERE, "fixtures", "scopes_small.json")
SEED = 4900000077
NAMES = ("beechnut_march_mxu_share", "beechnut_march_hbm_share",
         "beechnut_resident_MB", "beechnut_load_s")


def cell() -> dict:
    c = harness.find_files(
        {"name": "rehearsal-tiny-dataset16", "config": "tiny-dataset16",
         "traffic": "dataset-steer"}, home=HOME)
    return dict(c, chips=c["config_file"]["chips"])


def real() -> dict:
    return harness.load_cell("beechnut-u16-view")


def readers() -> dict:
    return {m.NAME: m for m in harness.load_layers() if m.NAME in NAMES}


@pytest.fixture(scope="module")
def rehearsed():
    """One traced rehearsal of the cell, its checks by name."""
    res = rehearse.rehearse(cell(), SEED, 1.0, True)
    return res, {n: (v, ok) for n, v, _, ok in res["checks"]}


def test_the_rehearsal_cell_is_correct(rehearsed):
    res, checks = rehearsed
    assert res["correct"], [n for n, (_, ok) in checks.items() if not ok]
    assert res["failed"] == 0
    assert checks["vdi_bytes_per_frame"][0] == 20 * 24 * 56 * 64
    assert checks["volume_resident_bytes"][0] == 26 * 40 * 48 * 2
    assert checks["field_dtype"][0] == "uint16"
    assert checks["field_max_abs_diff"][0] == 0
    assert checks["volume_copies_per_frame"][0] == 0      # a traced run
    assert checks["fallback_ledger_rows"][0] == 0
    assert checks["fallback_ledger_rows_reference"][0] == 0
    assert checks["compile_requests_in_window"][0] == 0
    assert 0.02 < checks["share_of_voxels_in_the_tf_support"][0] < 0.6
    assert "share_of_voxels_over_the_knee" not in checks
    # both f32 on the CPU: the raycast differs by the order of additions,
    # the reference session (the same march, the XLA fold) by less
    assert checks["raycast_psnr_dB_frame0"][0] > 100.0
    # the first steered frame from its own eye, by the same floor
    assert checks["raycast_psnr_dB_steered_frame"][0] > 100.0
    assert checks["decoded_psnr_dB_window_frame"][0] > 120.0


def test_the_control_is_not_correct():
    """The lowest bit dropped where the resident bytes would stand, the
    raycast in bfloat16 where the decoded frame would: the field's limit
    refuses it, and under the tent the image falls under the floor."""
    from chipbench import control

    res = control.read(cell(), SEED + 1, 1.0, "rounded", on_chip=False)
    checks = {n: (v, ok) for n, v, _, ok in res["checks"]}
    assert not res["correct"]
    assert checks["field_max_abs_diff"] == (1, False)
    floor = cell()["config_file"]["limits"]["raycast_psnr_floor_db"]
    for name in ("raycast_psnr_dB_frame0", "raycast_psnr_dB_steered_frame"):
        assert checks[name][0] < floor - 3 and not checks[name][1]


def test_shape_arithmetic_against_hand_numbers():
    shape = real()["config_file"]["shape"]
    assert arith.intermediate_grid(shape) == (1280, 1280)
    assert arith.vdi_bytes_per_frame(shape) == 786_432_000
    assert arith_dataset.volume_bytes(shape) == 3_242_196_992
    assert arith_dataset.volume_bytes(shape, "float32") == 6_484_393_984
    assert arith_dataset.march_floor_bytes_per_frame(shape) == \
        4_028_628_992
    dense = 1546 * (2 * 1280 * 1024 * 1024 + 2 * 1280 * 1024 * 1280)
    assert arith.march_dense_flops_per_frame(shape) == pytest.approx(dense)
    assert dense == pytest.approx(9.3375e12, rel=1e-4)
    assert arith_dataset16.operand_passes(2) == (2, 2)
    assert arith_dataset16.march_executed_flops_per_frame(shape, 2) == \
        pytest.approx(2 * dense)
    # a chunk that is itself the operand: one pass of each contraction
    assert arith_dataset16.operand_passes(1) == (1, 1)
    assert arith_dataset16.march_executed_flops_per_frame(shape, 1) == \
        pytest.approx(dense)


def test_the_passes_are_the_programs():
    """What `beechnut_march_mxu_share` counts against the program's own
    rule (`ops/slicer.operand_planes`): two planes for a u16 field that
    meets bf16 matmuls, one for every other field and where f32 operands
    are asked for. (That a recorded run counts them once per `dispatch`
    span: `tests/test_dataset_session.py -k operand`.)"""
    import jax.numpy as jnp

    from scenery_insitu_tpu.ops import slicer

    assert slicer.operand_planes(np.uint16) == 2
    assert slicer.operand_planes(np.uint16, "f32") == 1
    for dtype in (np.uint8, np.float32, jnp.bfloat16):
        assert slicer.operand_planes(dtype) == 1


def test_the_files_say_what_the_program_tables_say():
    import jax.numpy as jnp

    from scenery_insitu_tpu.core import transfer, volume

    c = real()
    conf = c["config_file"]
    source = harness.load_source(c)
    name = source.dataset_name(c)
    assert name == "beechnut" and conf["field_source"] == "raw_dataset_wide"
    assert tuple(reversed(conf["shape"]["grid"])) == \
        volume.DATASET_DIMS_XYZ[name]
    assert np.dtype(conf["shape"]["dtype"]) == volume.DATASET_DTYPES[name]
    assert conf["shape"]["grid"][0] % 16 == 10
    assert conf["reduced"] == [] and conf["chips"] == 1
    assert conf["control_overrides"] == []
    pts = conf["transfer_function"]["alpha"]
    x = np.linspace(0.0, 1.0, 1001, dtype=np.float32)
    rgb, alpha = transfer.for_dataset(name)(jnp.asarray(x))
    assert np.allclose(alpha, np.interp(x, *zip(*pts)), atol=2e-6)
    assert np.allclose(rgb, x[:, None], atol=1e-6)      # grays
    assert source.tf_support(pts) == [(0.43, 0.494)]
    # a ramp's support runs to the end of the table
    assert source.tf_support([[0.0, 0.0], [0.43, 0.0], [0.5, 0.005]]) == \
        [(0.43, 0.5)]
    kings = harness.load_cell("kingsnake-u8-view")["config_file"]
    assert conf["overrides"] == [
        o.replace("kingsnake", "beechnut") for o in kings["overrides"]]
    assert conf["reference_overrides"] == kings["reference_overrides"]
    assert conf["guarantees"].keys() == kings["guarantees"].keys()


def test_the_source_is_raw_datasets_code_but_for_its_parts():
    """`raw_dataset_wide` runs the accepted source's functions (compiled
    from the same file: the same bytecode) and replaces the widening,
    the reference session's field and the data's check; its
    `build_session`, `keep`, `compare` and `rounded` are the accepted
    ones with the steered frame beside frame 0."""
    from chipbench.sources import raw_dataset

    wide = harness.load_source(real())
    for part in ("slab", "generate", "write_file", "plain_reference",
                 "wait"):
        ours, theirs = (getattr(m, part).__code__
                        for m in (wide, raw_dataset))
        assert ours.co_filename == theirs.co_filename, part
        assert ours.co_code == theirs.co_code, part
    for part in ("window_checks", "build_session", "keep", "compare",
                 "rounded"):
        assert getattr(wide, part).__code__.co_filename != \
            getattr(raw_dataset, part).__code__.co_filename
    # the accepted module itself is left as it is
    assert raw_dataset.Widened.__name__ == "Widened"
    assert raw_dataset.raycast.__module__ == raw_dataset.__name__
    field = np.array([[[0, 65535], [29490, 1]]], np.uint16)
    got = np.asarray(wide.widened(field))
    assert got.dtype == np.float32
    assert np.array_equal(got, field.astype(np.float32) / 65535.0)
    fed = wide.Native(field)
    assert fed.field.dtype == np.uint16 and fed.static
    assert wide.share_in_tf_support(real(), field) == 0.25


@pytest.mark.parametrize("rule", [None, lambda dtype: 1])
def test_a_program_that_rounds_the_chunk_fails_at_once(rule, monkeypatch):
    """A program that has no rule for the operand planes of a dtype (PR
    49's parent: f32 operands, one bf16 pass on a TPU) or says ONE for
    u16 gets `no field source` before any data is made; what it would
    render is failed by the raycast floor too (README_dataset16.md: read
    on the chip with this look taken out)."""
    from scenery_insitu_tpu.ops import slicer

    source = harness.load_source(cell())
    assert source.operand_planes(cell()) == 2
    if rule is None:
        monkeypatch.delattr(slicer, "operand_planes")
    else:
        monkeypatch.setattr(slicer, "operand_planes", rule)
    monkeypatch.setattr(source._BASE, "write_file", lambda *a: 1 / 0)
    with pytest.raises(harness.BenchFailure, match="no field source"):
        source.build_session(cell(), harness.overrides_of(cell()), 1)


def test_the_data_are_what_the_configuration_says():
    """The seeded volume at the rehearsal size: the same geometry for two
    seeds, tissue INSIDE the tent with its structure in the low byte, air
    under it, the core over it."""
    c = cell()
    source = harness.load_source(c)
    a, b = (source.plain_reference(c, s)["field0"] for s in (1, 2))
    assert a.shape == (26, 40, 48) and a.dtype == np.uint16
    assert not np.array_equal(a, b)
    lo, hi = 0.43 * 65535, 0.494 * 65535
    inside = (a > lo) & (a < hi)
    assert np.mean(inside != ((b > lo) & (b < hi))) < 0.03
    assert 0.02 < source.share_in_tf_support(c, a) < 0.6
    assert len(np.unique(a[inside] & 0xFF)) > 200       # the low byte
    assert len(np.unique(a[inside] >> 8)) <= 18         # 0x6E..0x7E
    noise = c["traffic_file"]["field_noise"]
    assert (a[:, :3, :3] <= noise["air_high"] * 65535 + 1).all()
    assert a.max() >= 0.85 * 65535                      # the core


def test_march_shares_on_the_fixture(monkeypatch):
    """march 20 ms + fold 15 ms a frame on the fixture: the executed
    FLOPs of the cell's shape over the march's 20 ms and the bf16 peak;
    the floor bytes over 35 ms and 819 GB/s; nothing without a table."""
    monkeypatch.setattr(scopes, "table", lambda: (FIX["hlo_scopes"],
                                                  FIX["hlo_inherited"]))
    ctx = lambda: {
        "trace": xplane.Trace(FIX["events"]), "spans": FIX["spans"],
        "frames": FIX["frames"],
        "config": {"programs": real()["config_file"]["programs"]},
        "shape": real()["config_file"]["shape"],
        "peaks": arith.peaks_for("TPU v5 lite")}
    from scenery_insitu_tpu import obs

    rec = obs.Recorder(enabled=True)
    was = obs.set_recorder(rec)
    try:
        # no counter (PR 49's parent): nothing, and it says so
        assert readers()["beechnut_march_mxu_share"].read(ctx()) is None
        for frame in range(3):
            with rec.span("dispatch", frame=frame):
                rec.count("march_operand_planes", 2)
        got = readers()["beechnut_march_mxu_share"].read(ctx())
    finally:
        obs.set_recorder(was)
    assert got == pytest.approx(2 * 9.3375e12 / 0.020 / 197e12 * 100,
                                rel=1e-4)
    hbm = readers()["beechnut_march_hbm_share"].read(ctx())
    assert hbm == pytest.approx(4_028_628_992 / 0.035 / 819e9 * 100)
    monkeypatch.setattr(scopes, "table", lambda: ({}, {}))
    assert readers()["beechnut_march_hbm_share"].read(ctx()) is None


def test_the_recorders_readers_on_canned_sources(capsys):
    """`beechnut_load_s` and `beechnut_resident_MB` read the program's
    recorder through the accepted readers' code; where the program has no
    such span or counter they give nothing and say so."""
    from scenery_insitu_tpu import obs

    rec = obs.Recorder(enabled=True)
    was = obs.set_recorder(rec)
    try:
        for name in NAMES[2:]:
            assert readers()[name].read({}) is None
        assert capsys.readouterr().err.count("MISSING SOURCE") == 2
        with rec.span("dataset.load", frame=0) as span:
            span.note(bytes=3242196992, dtype="uint16", parts=8,
                      read_s=2.0, put_s=1.5)
        rec.count("volume_resident_bytes", 3242196992)
        load = [e for e in rec.events if e["name"] == "dataset.load"][0]
        assert readers()["beechnut_load_s"].read({}) == load["dur"]
        assert readers()["beechnut_resident_MB"].read({}) == 3242.196992
    finally:
        obs.set_recorder(was)


def test_the_entries_name_the_cell_alone():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, mod in readers().items():
        assert entries[name]["workloads"] == mod.CELLS == [
            "beechnut-u16-view"]
    assert sorted(readers()) == sorted(NAMES)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells["beechnut-u16-view"]["chips"] == 1
    assert cells["gs512-orbit"] == dict(
        cells["gs512-orbit"], config="gs512-1chip", traffic="orbit-steer",
        chips=1)
    assert len(cells) == 9 and sum(
        w["chips"] == 4 for w in cells.values()) == 3
