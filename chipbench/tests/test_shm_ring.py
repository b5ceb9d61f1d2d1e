"""The field source `shm_ring` at a rehearsal size (`tiny-shmring`: 32^3, a
real channel, a real producer process, the program's uploader): a sound run
is `correct` with its checks under their names in their order, the four
`ingest` readers read what the window held, the bf16 control is not
`correct`, a field altered on its way fails the exact check, and nothing the
source started outlives the run, also when the run fails.

`tiny-shmring` lives in `rehearsal/shm/configs/`, not beside the other
rehearsal configurations: `test_files.py` pins the list of those, and this PR
may not edit it."""

import hashlib
import os
import tempfile

import numpy as np
import pytest

from chipbench import control, harness

SEED = 2_147_483_659
HOME = os.path.join(harness.HERE, "rehearsal", "shm")
PSNR = {"decoded_psnr_dB_warmup_frame", "decoded_psnr_dB_window_frame"}
# the printed checks, in order: the harness's own (test_correct.py's CHECKS)
# with this source's where the sim's stand
CHECKS = ["frames_delivered_once_in_order", "frames_failed",
          "vdi_bytes_per_frame", "fallback_ledger_rows",
          "compile_requests_in_window", "ingest_fields_in_order",
          "producer_frames_dropped", "ingest_fields_repeated",
          "steering_answers_in_window", "host_field_frame0_max_abs_diff",
          "host_field_last_slabs_differing",
          "decoded_psnr_dB_warmup_frame", "decoded_psnr_dB_window_frame",
          "fallback_ledger_rows_reference"]
READERS = ("ingest_wait_ms", "upload_ms", "h2d_MB_per_frame",
           "upload_GB_per_s")


def failed_checks(res) -> set:
    return {name for name, _, _, ok in res["checks"] if not ok}


def cell(name: str = "rehearsal-tiny-shmring") -> dict:
    c = harness.find_files({"name": name, "config": "tiny-shmring",
                            "traffic": "ingest-steer"}, home=HOME)
    return dict(c, chips=c["config_file"]["chips"])


def run(c: dict, trace: bool = False, sabotage=None) -> dict:
    r = harness.open_run(c, SEED, trace, on_chip=False, verbose=False)
    if sabotage is not None:
        sabotage(r.sess)
    failed, layers, produced = harness.run_window(r, 0.3)
    harness.compare(r, produced, harness.references(c, SEED, produced))
    return harness.result(r, failed, layers)


def nothing_left(c: dict, proc) -> None:
    assert proc.poll() is not None              # killed and reaped
    assert not os.path.exists("/dev/shm" + harness.load_source(
        c).channel_name(c))


def test_the_cell_finds_its_files(monkeypatch, tmp_path):
    c = harness.load_cell("shm512-ingest")
    conf, traf = c["config_file"], c["traffic_file"]
    assert conf["field_source"] == "shm_ring" and conf["channel_slots"] == 2
    assert conf["shape"]["grid"] == [512, 512, 512]
    assert conf["limits"]["field_max_abs_diff"] == 0.0
    assert (traf["pre_evolve_steps"], traf["field_period_frames"],
            traf["field_amplitude"]) == (500, 64, 0.05)
    steer = harness.load_json(harness.HERE, "traffic", "insitu10-steer.json")
    for key in ("steering", "warmup_frames", "reference_frame",
                "sampled_frames", "trace_max_frames", "trace_max_seconds"):
        assert traf[key] == steer[key], key
    source = harness.load_source(c)
    # the cell's name and the run's own ground: this checkout, this TMPDIR
    ground = hashlib.sha1((harness.ROOT + "\0" + tempfile.gettempdir()
                           ).encode()).hexdigest()[:10]
    assert source.channel_name(c) == "/chipbench_shm512-ingest_" + ground
    assert source.channel_name(cell()) == (
        "/chipbench_rehearsal-tiny-shmring_" + ground)
    mine = source.channel_name(c)
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    assert source.channel_name(c) != mine   # another run's ground
    monkeypatch.undo()
    assert source.channel_name(c) == mine
    assert float(source.factor(0, traf)) == 1.0
    readers = {m.NAME: m for m in harness.load_layers()}
    for name in READERS:
        assert readers[name].CELLS == ["shm512-ingest"]
        assert readers[name].LAYER == "ingest"


def test_a_sound_run_is_correct_and_leaves_nothing():
    c = cell()
    r = harness.open_run(c, SEED, False, on_chip=False, verbose=False)
    proc = r.sess.shm_ring.proc
    assert proc.poll() is None
    assert not os.path.exists(                  # unlinked once attached
        "/dev/shm" + r.source.channel_name(c))
    failed, layers, produced = harness.run_window(r, 0.3)
    nothing_left(c, proc)
    harness.compare(r, produced, harness.references(c, SEED, produced))
    res = harness.result(r, failed, layers)
    assert res["correct"], failed_checks(res)
    assert res["failed"] == 0 and res["attempted"] >= 8
    assert [name for name, *_ in res["checks"]] == CHECKS
    values = {name: value for name, value, *_ in res["checks"]}
    assert values["host_field_frame0_max_abs_diff"] == 0.0
    assert values["host_field_last_slabs_differing"] == 0
    done, total = values["ingest_fields_in_order"].split("/")
    assert done == total and int(total) >= res["attempted"] + 12


def test_the_ingest_readers_read_the_window():
    """Traced, under the cell's own name so that the readers' `CELLS` let
    them run: one upload of one field per frame, to the byte."""
    res = run(cell("shm512-ingest"), trace=True)
    assert res["correct"], failed_checks(res)
    got = {k: v[0] for k, v in res["per_layer"].items()}
    assert set(READERS) <= set(got)
    assert got["h2d_MB_per_frame"] == 32 ** 3 * 4 / 1e6
    assert got["upload_ms"] > 0 and got["ingest_wait_ms"] >= 0
    assert got["upload_GB_per_s"] == pytest.approx(
        got["h2d_MB_per_frame"] / got["upload_ms"], rel=1e-6)
    assert "sim_dispatch_ms" in got and "sim_device_ms" not in got


@pytest.mark.parametrize("seed", [3, 4])
def test_the_control_is_not_correct(seed):
    """Field 0 and the reference's frames held in bfloat16."""
    res = control.read(cell(), seed, 0.3, "rounded", on_chip=False)
    assert not res["correct"]
    assert failed_checks(res) == PSNR | {"host_field_frame0_max_abs_diff"}


@pytest.mark.parametrize("first", [0, 1])
def test_a_field_altered_on_its_way_to_the_device(first):
    """Scaled by 1 - 1e-3 between the slot and the device: from field 0 on
    (it has landed when the session is built) both exact checks fail;
    from field 1 on, the one of the window's last field and the decoded
    frames."""
    def sabotage(sess):
        src, land = sess.sim, sess.sim._land
        src._land = lambda view: land(view * np.float32(0.999))
        if first == 0:
            with src._cond:
                field0, seq = src._landed
                src._landed = (field0 * np.float32(0.999), seq)

    res = run(cell(), sabotage=sabotage)
    assert not res["correct"]
    assert failed_checks(res) == PSNR | {
        "host_field_last_slabs_differing"} | (
        {"host_field_frame0_max_abs_diff"} if first == 0 else set())
    values = {name: value for name, value, *_ in res["checks"]}
    assert values["host_field_last_slabs_differing"] == 8


def test_a_field_torn_on_its_way_to_the_device():
    """From field 1 on, the first planes come out as another field's (what
    a slot overwritten under the upload would leave): one slab of the
    window's last field differs, whatever the decoded frames make of it."""
    def sabotage(sess):
        land = sess.sim._land

        def torn(view):
            view = view.copy()
            view[:2] *= np.float32(1.01)
            return land(view)

        sess.sim._land = torn

    res = run(cell(), sabotage=sabotage)
    assert not res["correct"]
    assert "host_field_last_slabs_differing" in failed_checks(res)
    assert "host_field_frame0_max_abs_diff" not in failed_checks(res)
    values = {name: value for name, value, *_ in res["checks"]}
    assert values["host_field_last_slabs_differing"] == 1


def test_a_field_rendered_twice():
    """One frame of the warm-up takes no field and renders the one before
    again: a field fewer than frames, and every later frame one field
    behind the reference's."""
    def sabotage(sess):
        take = sess.sim.advance

        def advance(n):
            if sess.frame_index != 5:
                take(n)

        sess.sim.advance = advance

    res = run(cell(), sabotage=sabotage)
    assert not res["correct"]
    assert failed_checks(res) == {"ingest_fields_in_order",
                                  "decoded_psnr_dB_window_frame"}


def test_a_run_that_fails_leaves_nothing_either():
    c = cell()
    r = harness.open_run(c, SEED, False, on_chip=False, verbose=False)
    proc = r.sess.shm_ring.proc
    r.sess.run = None                           # the first frame raises
    with pytest.raises(TypeError):
        harness.run_window(r, 0.3)
    nothing_left(c, proc)
    assert not hasattr(r, "sess")
