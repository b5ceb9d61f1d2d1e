"""What decides `correct`, at a size a test run can hold (32^3 on the CPU,
the rehearsal configurations): a sound run passes, on one rank, on a mesh
of four, from a field the session does not compute and with set-up steps
before frame 0; the control, the configuration's lower-precision path
switched on, does not; and with the timed path broken underneath, `correct`
comes out false."""

import copy

import numpy as np
import pytest

from chipbench import control, harness, reference
from chipbench.rehearse import rehearsal_cell

SEED = 2_147_483_659        # more than 32 signed bits hold


def run(ranks=1, sabotage=None, cell=None, timed=None):
    """The harness's own chain of a run (`harness.run_cell`) without its
    look for a chip; `sabotage(sess, sink)` breaks the timed path
    underneath before the first frame, and `timed` is a broken cell the
    timed path is built from while the references keep `cell`."""
    cell = cell or rehearsal_cell(ranks)
    r = harness.open_run(timed or cell, SEED, False, on_chip=False,
                         verbose=False)
    if sabotage is not None:
        sabotage(r.sess, r.sink)
    failed, layers, produced = harness.run_window(r, 0.3)
    harness.compare(r, produced, harness.references(cell, SEED, produced))
    return harness.result(r, failed, layers)


def failed_checks(res) -> set:
    return {name for name, _, _, ok in res["checks"] if not ok}


PSNR = {"decoded_psnr_dB_warmup_frame", "decoded_psnr_dB_window_frame"}
# the printed checks, in order: the harness's own, with the field source's
# where the sim's stood before the sources were split off
CHECKS = ["frames_delivered_once_in_order", "frames_failed",
          "vdi_bytes_per_frame", "fallback_ledger_rows",
          "compile_requests_in_window", "{window}",
          "steering_answers_in_window", "{field}",
          "decoded_psnr_dB_warmup_frame", "decoded_psnr_dB_window_frame",
          "fallback_ledger_rows_reference"]
SOURCE_CHECKS = {
    "tiny-1rank": ("sim_state_devices", "sim_field_frame0_max_abs_diff"),
    "tiny-4rank": ("sim_state_devices", "sim_field_frame0_max_abs_diff"),
    "tiny-hostring": ("host_fields_put_by_frame0",
                      "host_field_frame0_max_abs_diff")}


@pytest.mark.parametrize("config", sorted(SOURCE_CHECKS))
def test_a_sound_run_is_correct(config):
    """Every rehearsal configuration, whatever its field source, through
    the same chain: one rank, a mesh of four, a field the session does not
    compute. The printed checks keep their names and order; the two
    configurations from before the sources were split off print what they
    printed then."""
    res = run(cell=rehearsal_cell(config=config))
    assert res["correct"], failed_checks(res)
    assert res["failed"] == 0 and res["attempted"] >= 8
    assert res["end_to_end"]["fps"][0] > 0
    window, field = SOURCE_CHECKS[config]
    assert [name for name, *_ in res["checks"]] == [
        c.format(window=window, field=field) for c in CHECKS]


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_the_control_is_not_correct(seed):
    """The plain references held in bfloat16 where the program's frames and
    field would stand: every one of the cell's numbers fails."""
    res = control.read(rehearsal_cell(1), seed, 0.3, "rounded",
                       on_chip=False)
    assert not res["correct"]
    assert failed_checks(res) == PSNR | {"sim_field_frame0_max_abs_diff"}


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_the_control_of_a_field_the_session_does_not_compute(seed):
    """`tiny-hostring`: the ring and the reference frames held in bfloat16
    fail the source's own exact comparison and both frames."""
    res = control.read(rehearsal_cell(config="tiny-hostring"), seed, 0.3,
                       "rounded", on_chip=False)
    assert not res["correct"]
    assert failed_checks(res) == PSNR | {"host_field_frame0_max_abs_diff"}


def test_a_source_ends_what_it_started_also_when_the_run_fails():
    cell = rehearsal_cell(config="tiny-hostring")
    r = harness.open_run(cell, SEED, False, on_chip=False, verbose=False)
    sim = r.sess.sim
    harness.run_window(r, 0.3)
    assert sim.ended and not hasattr(r, "sess")
    r = harness.open_run(cell, SEED, False, on_chip=False, verbose=False)
    sim = r.sess.sim
    r.sess.run = None       # the first frame raises
    with pytest.raises(TypeError):
        harness.run_window(r, 0.3)
    assert sim.ended and not hasattr(r, "sess")


def test_a_host_field_altered_on_its_way_to_the_device():
    def sabotage(sess, sink):
        sess.sim.fields = [f * np.float32(0.999) for f in sess.sim.fields]

    res = run(cell=rehearsal_cell(config="tiny-hostring"), sabotage=sabotage)
    assert not res["correct"]
    assert "host_field_frame0_max_abs_diff" in failed_checks(res)


def orbit_cell() -> dict:
    return rehearsal_cell(traffic="orbit-steer")


def test_setup_steps_are_taken_by_run_and_reference_alike():
    """`orbit-steer` at the rehearsal size: 500 steps before frame 0, none
    in any frame; the field after them against 500 steps of the plain
    roll, and the reference session fed that roll's state."""
    cell = orbit_cell()
    assert cell["traffic_file"]["pre_evolve_steps"] == 500
    assert harness.overrides_of(cell)[-1] == "sim.steps_per_frame=0"
    res = run(cell=cell)
    assert res["correct"], failed_checks(res)
    quiet = run()       # the same checks as a cell whose sim runs
    assert [c[0] for c in res["checks"]] == [c[0] for c in quiet["checks"]]


def test_a_run_whose_setup_steps_are_skipped_is_not_correct():
    cell = orbit_cell()
    timed = copy.deepcopy(cell)
    timed["traffic_file"]["pre_evolve_steps"] = 0
    res = run(cell=cell, timed=timed)
    assert not res["correct"]
    assert failed_checks(res) == PSNR | {"sim_field_frame0_max_abs_diff"}


def test_a_field_that_moves_where_the_traffic_holds_it():
    """The traffic's `sim.steps_per_frame=0` dropped from the timed path:
    the sim advances in every frame of a cell whose field stands still."""
    cell = orbit_cell()
    timed = copy.deepcopy(cell)
    timed["traffic_file"]["overrides"] = []
    res = run(cell=cell, timed=timed)
    assert not res["correct"]
    assert "sim_field_frame0_max_abs_diff" in failed_checks(res)


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_the_control_after_setup_steps_is_not_correct(seed):
    res = control.read(orbit_cell(), seed, 0.3, "rounded", on_chip=False)
    assert not res["correct"]
    assert failed_checks(res) == PSNR | {"sim_field_frame0_max_abs_diff"}


def test_the_programs_own_lower_precision_is_not_correct():
    """`composite.wire=bf16`: the exchange between ranks in bfloat16, a
    path the program has, on the mesh of four."""
    res = control.read(rehearsal_cell(4), SEED, 0.3, "program",
                       on_chip=False)
    assert not res["correct"]
    assert failed_checks(res) == PSNR


def test_a_fault_that_starts_after_the_warmup_frame():
    """Colours scaled by 1 - 1e-3 from frame 3 on: the warm-up frame
    (2, viewer idle) is sound, the steered frame of the window is not."""
    def sabotage(sess, sink):
        fetch = sess._fetch

        def altered(index, out):
            if index > 2:
                out = out._replace(color=out.color * 0.999)
            return fetch(index, out)

        sess._fetch = altered

    res = run(sabotage=sabotage)
    assert not res["correct"]
    assert failed_checks(res) == {"decoded_psnr_dB_window_frame"}


def test_seeds_change_the_data_not_the_layout():
    a = reference.gray_scott_frame0((32, 32, 32), 3, 10, amplitude=1e-3)
    b = reference.gray_scott_frame0((32, 32, 32), 4, 10, amplitude=1e-3)
    same = reference.gray_scott_frame0((32, 32, 32), 3, 10, amplitude=1e-3)
    assert np.array_equal(a, same) and not np.array_equal(a, b)
    assert np.abs(a - b).max() < 1e-2
    assert np.array_equal(a > 1e-3, b > 1e-3)


def test_a_step_that_returns_its_state_unchanged():
    def sabotage(sess, sink):
        sess.sim.advance = lambda n: None

    res = run(sabotage=sabotage)
    assert not res["correct"]
    assert "sim_field_frame0_max_abs_diff" in failed_checks(res)


def test_an_answer_altered_where_it_is_produced():
    """The fetched colours scaled by 1 - 1e-3 before the sink sees them."""
    def sabotage(sess, sink):
        fetch = sess._fetch

        def altered(index, out):
            return fetch(index, out._replace(color=out.color * 0.999))

        sess._fetch = altered

    res = run(sabotage=sabotage)
    assert not res["correct"]
    assert PSNR <= failed_checks(res)


def test_a_frame_that_never_reaches_the_sink():
    def sabotage(sess, sink):
        inner = sess.sinks[0]

        def lossy(index, payload):
            if index != 40:
                inner(index, payload)

        sess.sinks[0] = lossy

    res = run(sabotage=sabotage)
    assert not res["correct"] and res["failed"] > 0
    assert "frames_delivered_once_in_order" in failed_checks(res)


def unpinned(ranks: int) -> dict:
    cell = rehearsal_cell(ranks)
    cell["config_file"]["overrides"] = [
        o for o in cell["config_file"]["overrides"]
        if o != "sim.fused_stencil=false"]
    return cell


def test_a_fallback_ledger_row():
    """The fused stencil asked for on the CPU gives way on the ledger."""
    res = run(cell=unpinned(1))
    assert not res["correct"]
    assert "fallback_ledger_rows" in failed_checks(res)


def test_a_ledger_row_the_configuration_admits_and_one_it_does_not():
    """`gs512-4rank` as it stands: the fused stencil left on, the sharded
    state takes the roll and the program says so on the ledger; the
    configuration's guarantees admit that one row by its component, and a
    run is held to no row but those."""
    admits = harness.load_json(harness.HERE, "configs", "gs512-4rank.json")[
        "guarantees"]["fallback_ledger_admits"]
    assert list(admits) == ["sim.fused_stencil"]
    cell = unpinned(4)
    res = run(cell=cell)
    assert {"fallback_ledger_rows", "fallback_ledger_rows_reference"} \
        <= failed_checks(res)
    cell["config_file"]["guarantees"]["fallback_ledger_admits"] = admits
    res = run(cell=cell)
    assert res["correct"], failed_checks(res)
    cell["config_file"]["guarantees"]["fallback_ledger_admits"] = {
        "slicer.fold": "another component's row"}
    assert not run(cell=cell)["correct"]
