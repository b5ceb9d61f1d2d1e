"""The vortex configuration (PR 37): its cell finds its files, the
rehearsal size (`tiny-vortex-4rank`: 32^3 over four virtual devices, the
cell's own field source) is `correct` and its bf16 control is not, the six
`vortex_*` readers give the hand-computed answers on
`fixtures/scopes_vortex.json` and nothing without a table, and the plain
reference stands alone.

`tiny-vortex-4rank` lives in `rehearsal/vortex/configs/`, not beside the
other rehearsal configurations: `test_files.py` pins the list of those, and
this PR may not edit it (PR 33's `rehearsal/shm/` is the pattern)."""

import ast
import os

import numpy as np
import pytest

from chipbench import arith, control, harness, reference_vortex, scopes
from chipbench import xplane
from chipbench.rehearse import rehearse

SEED = 2_147_483_659
HOME = os.path.join(harness.HERE, "rehearsal", "vortex")
FIX = harness.load_json(harness.HERE, "fixtures", "scopes_vortex.json")
PROGRAMS = {"sim": "^jit_vortex_frame", "step": r"^jit_step\("}
# the printed checks, in order: the harness's own with this source's
CHECKS = ["frames_delivered_once_in_order", "frames_failed",
          "vdi_bytes_per_frame", "fallback_ledger_rows",
          "compile_requests_in_window", "sim_state_devices",
          "steering_answers_in_window", "sim_field_frame0_max_abs_diff",
          "decoded_psnr_dB_warmup_frame", "decoded_psnr_dB_window_frame",
          "fallback_ledger_rows_reference"]
# ms per run of the sim program (it ran once in the fixture's window)
WANT = {
    "vortex_sim_device_ms": 60.0,
    "vortex_advect_device_ms": 30.0,        # fusion 4 + all-gather 6 + gather 20
    "vortex_project_device_ms": 12.0,
    "vortex_field_device_ms": 10.0,         # fusion 8 + all-reduce 2
    # [12, 20) the async all-gather and the op, [63, 65) the all-reduce;
    # the step program's all-to-all is not the sim's
    "vortex_sim_collective_ms": 10.0,
    # 469,762,048 B / 4 ranks over 60 ms and 819 GB/s
    "vortex_sim_hbm_share": 469762048 / 4 / 0.060 / 819e9 * 100.0,
}


def cell() -> dict:
    c = harness.find_files(
        {"name": "rehearsal-tiny-vortex-4rank",
         "config": "tiny-vortex-4rank", "traffic": "insitu10-steer"},
        home=HOME)
    return dict(c, chips=c["config_file"]["chips"])


def ctx(table=(FIX["hlo_scopes"], FIX["hlo_inherited"]), monkeypatch=None):
    if monkeypatch is not None:
        monkeypatch.setattr(scopes, "table", lambda: table)
    return {"trace": xplane.Trace(FIX["events"]), "spans": [],
            "frames": FIX["frames"], "config": {"programs": PROGRAMS},
            "shape": FIX["shape"], "peaks": arith.peaks_for("TPU v5 lite")}


def readers() -> dict:
    return {m.NAME: m for m in harness.load_layers() if m.NAME in WANT}


def test_the_cell_finds_its_files():
    c = harness.load_cell("vortex256-4rank-insitu")
    conf = c["config_file"]
    assert (c["chips"], conf["chips"], conf["reduced"]) == (4, 4, [])
    assert conf["field_source"] == "sim_vortex"
    assert conf["shape"]["grid"] == [256, 256, 256]
    assert conf["solver"] == {"dt": 0.1, "viscosity": 0.001}
    assert "fallback_ledger_admits" not in conf["guarantees"]
    assert "sim.dt=0.1" in conf["overrides"]
    assert arith.intermediate_grid(conf["shape"]) == (320, 320)
    assert arith.vdi_bytes_per_frame(conf["shape"]) == 39_321_600
    tiny = cell()["config_file"]
    for key in ("field_source", "solver", "programs", "reference_overrides",
                "control_overrides"):
        assert tiny[key] == conf[key], key
    swap = lambda o: o.replace("[256,256,256]", "[32,32,32]").replace(
        "supersegments=16", "supersegments=8")
    assert tiny["overrides"] == [swap(o) for o in conf["overrides"]]
    entries = {m["name"]: m for m in c["bench"]["per_layer"]}
    assert set(WANT) <= set(entries) and set(readers()) == set(WANT)
    for name in WANT:
        assert entries[name]["workloads"] == ["vortex256-4rank-insitu"]


def test_the_floor_bytes_of_the_published_size():
    share = readers()["vortex_sim_hbm_share"]
    shape = harness.load_cell("vortex256-4rank-insitu")["config_file"][
        "shape"]
    assert share.floor_bytes(shape) == 469_762_048
    assert share.floor_bytes(dict(shape, steps_per_frame=2)) == \
        13 * 4 * 256 ** 3


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_the_fixture(name, monkeypatch):
    got = readers()[name].read(ctx(monkeypatch=monkeypatch))
    assert got == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", [
    "vortex_advect_device_ms", "vortex_project_device_ms",
    "vortex_field_device_ms"])
def test_a_sim_program_without_a_table_reads_nothing(name, monkeypatch,
                                                     capsys):
    """A commit whose sim program is not scoped keeps no table of it: the
    readers give nothing (not 0) and stderr says that a source is missing,
    also where only ANOTHER program left a table. With the table, a scope
    in which no op ran reads 0, and the sim's unscoped copy is nobody's."""
    for table in (({}, {}), ({"jit_step": FIX["hlo_scopes"]["jit_step"]},
                             {})):
        got = readers()[name].read(ctx(table=table, monkeypatch=monkeypatch))
        assert got is None
        assert "MISSING SOURCE" in capsys.readouterr().err
    table = ({"jit_vortex_frame": {"copy.5": "sim_nothing"}}, {})
    assert readers()[name].read(ctx(table=table,
                                    monkeypatch=monkeypatch)) == 0.0
    from chipbench import sim_scopes

    got = sim_scopes.sim(ctx(monkeypatch=monkeypatch))
    assert got["kinds"][None] == {"copy": pytest.approx(0.003)}
    assert got["program"] == pytest.approx(0.060)
    assert got["ops"] == pytest.approx(0.055)


def test_the_trace_readers_read_nothing_where_no_sim_program_ran(
        monkeypatch):
    none = dict(ctx(monkeypatch=monkeypatch),
                config={"programs": dict(PROGRAMS, sim="^jit_absent")})
    for name in ("vortex_sim_device_ms", "vortex_sim_collective_ms",
                 "vortex_sim_hbm_share"):
        assert readers()[name].read(dict(none)) is None


def test_the_reference_stands_alone():
    """`reference_vortex.py` imports nothing of the program, its start is
    divergence-free and its step stays so, and holding the state in
    bfloat16 moves the rendered field by thousands of the f32 rounding."""
    with open(reference_vortex.__file__) as f:
        tree = ast.parse(f.read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names] + [n.module for n in ast.walk(tree)
                                  if isinstance(n, ast.ImportFrom)]
    assert not [n for n in names if "scenery_insitu_tpu" in n]
    grid = (16, 24, 32)
    u0 = reference_vortex.start(grid, SEED, 1e-3)
    u1 = reference_vortex.steps(u0, 1)

    def div(u):     # spectral, as the projection removes it
        kz, ky, kx = reference_vortex.wavenumbers(grid)
        uh = [np.fft.rfftn(c) for c in u]
        return np.abs(np.fft.irfftn(1j * (kx * uh[0] + ky * uh[1]
                                          + kz * uh[2]), s=grid,
                                    axes=(0, 1, 2))).max()

    assert div(u0) < 1e-5 and div(u1) < 1e-5
    assert np.abs(u1 - u0).max() > 1e-2          # the rings moved
    f32 = reference_vortex.frame0(grid, SEED, 1e-3, 1)
    bf16 = reference_vortex.frame0(grid, SEED, 1e-3, 1, dtype="bfloat16")
    assert 0.0 <= f32.min() and 0.99 < f32.max() <= 1.0
    assert np.abs(f32 - bf16).max() > 1e-3
    other = reference_vortex.frame0(grid, SEED + 1, 1e-3, 1)
    assert 0 < np.abs(f32 - other).max() < 1e-2  # the seed's own data


def test_the_source_fails_cleanly_on_a_program_without_the_frame_program(
        monkeypatch):
    """The benchmark's files laid over a checkout from before PR 37: no
    result, said in one line, before a session is built."""
    from scenery_insitu_tpu.sim import vortex

    monkeypatch.delattr(vortex, "frame_program")
    with pytest.raises(harness.BenchFailure, match="no frame_program"):
        harness.open_run(cell(), SEED, False, on_chip=False, verbose=False)


def test_rehearsal_is_correct_and_the_control_is_not():
    c = cell()
    res = rehearse(c, SEED, 0.3, False)
    assert [n for n, *_ in res["checks"]] == CHECKS
    assert res["correct"] and res["failed"] == 0
    by = {n: v for n, v, _, _ in res["checks"]}
    assert by["sim_state_devices"] == 4
    assert by["sim_field_frame0_max_abs_diff"] <= 5e-6     # f32 on the CPU
    assert by["vdi_bytes_per_frame"] == arith.vdi_bytes_per_frame(
        c["config_file"]["shape"])
    bad = control.read(c, SEED, 0.3, "rounded", on_chip=False)
    assert not bad["correct"]
    failed = {n for n, _, _, ok in bad["checks"] if not ok}
    assert failed == {"sim_field_frame0_max_abs_diff",
                      "decoded_psnr_dB_warmup_frame",
                      "decoded_psnr_dB_window_frame"}
    by = {n: v for n, v, _, _ in bad["checks"]}
    assert by["sim_field_frame0_max_abs_diff"] > 5 * c["config_file"][
        "limits"]["sim_atol"]


def test_a_traced_rehearsal_reads_the_sim_programs_table(monkeypatch):
    """With the recorder on, the sim executable leaves its table where the
    readers look for it (`scopes.table()`, read while the timed session
    lives), and the three scopes are in it; the device readers need a
    device plane, which a CPU trace has not."""
    seen = {}
    read_layers = harness.read_layers

    def spy(run):
        seen.update(scopes.table()[0])
        return read_layers(run)

    monkeypatch.setattr(harness, "read_layers", spy)
    res = rehearse(cell(), SEED, 0.3, True)
    assert res["correct"]
    assert not [n for n in res["per_layer"] if n.startswith("vortex_")]
    sim = [ops for m, ops in seen.items() if m.startswith(
        "jit_vortex_frame")]
    assert sim and {"sim_advect", "sim_project", "sim_field"} <= set(
        sim[0].values())
