"""What decides `correct`, at a size a test run can hold (32^3 on the CPU,
the rehearsal configurations): a sound run passes, on one rank and on a
mesh of four; the control, the configuration's lower-precision path
switched on, does not; and with the timed path broken underneath, `correct`
comes out false."""

import numpy as np
import pytest

from chipbench import control, harness, reference
from chipbench.rehearse import rehearsal_cell

SEED = 2_147_483_659        # more than 32 signed bits hold


def run(ranks=1, sabotage=None, cell=None):
    """The harness's own chain of a run (`harness.run_cell`) without its
    look for a chip; `sabotage(sess, sink)` breaks the timed path
    underneath before the first frame."""
    cell = cell or rehearsal_cell(ranks)
    r = harness.open_run(cell, SEED, False, on_chip=False, verbose=False)
    if sabotage is not None:
        sabotage(r.sess, r.sink)
    failed, layers, produced = harness.run_window(r, 0.3)
    harness.compare(r, produced, harness.references(cell, SEED, produced))
    return harness.result(r, failed, layers)


def failed_checks(res) -> set:
    return {name for name, _, _, ok in res["checks"] if not ok}


PSNR = {"decoded_psnr_dB_warmup_frame", "decoded_psnr_dB_window_frame"}


@pytest.mark.parametrize("ranks", [1, 4])
def test_a_sound_run_is_correct(ranks):
    res = run(ranks)
    assert res["correct"], failed_checks(res)
    assert res["failed"] == 0 and res["attempted"] >= 8
    assert res["end_to_end"]["fps"][0] > 0
    assert PSNR <= {name for name, *_ in res["checks"]}


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_the_control_is_not_correct(seed):
    """The plain references held in bfloat16 where the program's frames and
    field would stand: every one of the cell's numbers fails."""
    res = control.read(rehearsal_cell(1), seed, 0.3, "rounded",
                       on_chip=False)
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


def test_a_fallback_ledger_row():
    """The fused stencil asked for on the CPU gives way on the ledger."""
    cell = rehearsal_cell(1)
    cell["config_file"]["overrides"] = [
        o for o in cell["config_file"]["overrides"]
        if o != "sim.fused_stencil=false"]
    res = run(cell=cell)
    assert not res["correct"]
    assert "fallback_ledger_rows" in failed_checks(res)
