"""The readers PR 24 added, on `fixtures/scopes_small.json`: one device, two
programs, a `while`, a scoped and an unscoped op, two frames of spans. Every
answer is known by hand."""

import os

import pytest

from chipbench import harness, scopes, xplane

FIX = harness.load_json(harness.HERE, "fixtures", "scopes_small.json")
PROGRAMS = {"sim": "^jit_multi_step", "step": r"^jit_step\("}
# ms per frame, two frames; the step program ran once in the window
WANT = {
    "march_device_ms": 20.0,            # fusion.3 8 + what while.2 keeps, 12
    "fold_device_ms": 15.0,             # the kernel
    "composite_device_ms": 14.0,        # resegment 10 + merge 4
    "step_unscoped_share": 100 * (60 - 49) / 60,    # copy.5 5 + no op 6
    "fetch_ready_ms": 15.0,             # (20 + 10) / 2
    "fetch_copy_ms": 14.0,              # (10 + 6 + 2 + 1 + 9) / 2
    "fetch_concat_ms": 3.0,
    "fetch_shard_max_ms": 11.0,         # frame 1: shard 1 = 2 + 9; frame 0
                                        # has no shard and does not count
    "sim_dispatch_ms": 3.0,
    "step_dispatch_ms": 6.0,
}


TABLE = (FIX["hlo_scopes"], FIX["hlo_inherited"])


def ctx(table=TABLE, monkeypatch=None):
    if monkeypatch is not None:
        monkeypatch.setattr(scopes, "table", lambda: table)
    return {"trace": xplane.Trace(FIX["events"]), "spans": FIX["spans"],
            "frames": FIX["frames"], "config": {"programs": PROGRAMS}}


def readers() -> dict:
    return {m.NAME: m for m in harness.load_layers() if m.NAME in WANT}


def test_every_new_reader_has_its_answer():
    assert set(readers()) == set(WANT)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_the_fixture(name, monkeypatch):
    got = readers()[name].read(ctx(monkeypatch=monkeypatch))
    assert got == pytest.approx(WANT[name])


def test_by_scope_partitions_the_program():
    got = scopes.by_scope(xplane.Trace(FIX["events"]), PROGRAMS["step"],
                          *TABLE)
    assert got["program"] == pytest.approx(0.060)
    assert got["ops"] == pytest.approx(0.054)
    assert got["scopes"] == {"march": pytest.approx(0.020),
                             "fold": pytest.approx(0.015),
                             "resegment": pytest.approx(0.010),
                             "merge": pytest.approx(0.004)}
    # fusion.3 has no scope of its own: its 8 ms are the march's by
    # position (the `while` around it), and are kept apart
    assert got["inherited"] == {"march": {"fusion": pytest.approx(0.008)}}
    assert got["kinds"][None] == {"copy": pytest.approx(0.005)}
    assert got["kinds"]["march"] == {"fusion": pytest.approx(0.008),
                                     "while": pytest.approx(0.012)}
    assert sum(sum(per.values()) for per in got["kinds"].values()) \
        == pytest.approx(got["ops"])
    # an instruction name is unique within its module only: the sim
    # program's table also names a `fusion.3`, and the step's op is not
    # given its phase; the sim's own op has no entry and stays unscoped
    sim = scopes.by_scope(xplane.Trace(FIX["events"]), PROGRAMS["sim"],
                          FIX["hlo_scopes"])
    assert sim["scopes"] == {}
    assert sim["kinds"] == {None: {"gray_scott_fused_t4":
                                   pytest.approx(0.010)}}


@pytest.mark.parametrize("name", [
    "march_device_ms", "fold_device_ms", "composite_device_ms",
    "step_unscoped_share"])
def test_a_program_without_a_table_reads_nothing(name, monkeypatch, capsys):
    """A commit before PR 24 keeps no table: the readers do not raise and
    give nothing (0 ms would be the best a `lower` metric can read), and
    stderr says that a source is missing. With a table, a scope in which
    no op ran is 0: that is a reading."""
    got = readers()[name].read(ctx(table=({}, {}), monkeypatch=monkeypatch))
    assert got is None
    assert "MISSING SOURCE" in capsys.readouterr().err
    table = ({"jit_another_program": {"fusion.3": "march"}}, {})
    got = readers()[name].read(ctx(table=table, monkeypatch=monkeypatch))
    assert got == (100.0 if name == "step_unscoped_share" else 0.0)


NAMES = {"fetch_ready_ms": "fetch.ready", "fetch_copy_ms": "fetch.copy",
         "fetch_concat_ms": "fetch.concat", "sim_dispatch_ms": "sim",
         "step_dispatch_ms": "dispatch"}


@pytest.mark.parametrize("name", sorted(NAMES))
def test_a_span_absent_from_the_recorder_reads_nothing(name, capsys):
    """Spans were recorded but none of the reader's name (renamed, removed,
    not opened under this traffic, a commit from before it): the reader
    gives None, not 0, which is the best a `lower` metric can read, and
    stderr names the span; with no span at all there is nothing to read
    either, and a span that is there is read without a word."""
    others = [e for e in FIX["spans"] if e["name"] != NAMES[name]]
    c = dict(ctx(), spans=others)
    assert readers()[name].read(c) is None
    err = capsys.readouterr().err
    assert "MISSING SOURCE" in err and repr(NAMES[name]) in err
    assert readers()[name].read(dict(c, spans=[])) is None
    assert readers()[name].read(ctx()) > 0.0
    assert capsys.readouterr().err == ""


def test_a_traced_line_leaves_out_what_no_reader_read(monkeypatch, capsys):
    """`run.py` gives the result of a traced run whose readers found
    nothing for a declared per-layer metric, without that metric; it goes
    on refusing a run that lacks a declared end-to-end metric."""
    import json

    from chipbench import run

    res = {"correct": True, "attempted": 8, "failed": 0,
           "end_to_end": {"setup_s": (1.0, "s"), "fps": (2.0, "frames/s"),
                          "steer_to_pixel_ms": (4.0, "ms")},
           "per_layer": {"dispatch_ms": (3.0, "ms")},
           "device": {"platform": "tpu"}, "breakdown": None,
           "checks": [("frames_failed", 0, 0, True),
                      ("decoded_psnr_dB_window_frame", float("inf"), 120.0,
                       True)]}
    monkeypatch.setattr(harness, "run_cell", lambda *a: res)
    argv = ["--workload", "gs512-4rank-insitu", "--seed", "1", "--seconds",
            "1", "--trace"]
    assert run.main(argv + ["1"]) == 0
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line["metrics"]) == ["dispatch_ms"]
    assert "fetch_ready_ms" in err and "nothing to read" in err
    assert list(line)[-1] == "checks"
    assert line["checks"] == {"frames_failed": [0, 0],
                              "decoded_psnr_dB_window_frame": ["inf", 120.0]}
    assert err.strip().splitlines()[-1].startswith(
        "[chipbench] compared decoded_psnr_dB_window_frame: inf")
    res["end_to_end"].pop("fps")
    assert run.main(argv + ["0"]) == 1
    out, err = capsys.readouterr()
    assert "did not report ['fps']" in err and '"correct"' not in out


def test_table_is_the_recorders():
    from scenery_insitu_tpu import obs

    prev = obs.get_recorder()
    rec = obs.Recorder(enabled=True)
    rec.hlo_scopes["jit_step"] = {"fusion.1": "march", "copy.2": "march"}
    rec.hlo_inherited["jit_step"] = {"copy.2"}
    obs.set_recorder(rec)
    try:
        assert scopes.table() == (
            {"jit_step": {"fusion.1": "march", "copy.2": "march"}},
            {"jit_step": {"copy.2"}})
    finally:
        obs.set_recorder(prev)
    del rec.hlo_scopes, rec.hlo_inherited   # a recorder from before PR 24
    obs.set_recorder(rec)
    try:
        assert scopes.table() == ({}, {})
    finally:
        obs.set_recorder(prev)


def test_idle_gaps_name_a_child_only_where_it_covers_the_whole_gap():
    """`idle_gaps` names a gap after the span that covers MOST of it, and
    a parent covers at least what its child does: the child wins only on
    a tie, a gap that lies wholly inside it (15..20 ms here; the recorder
    writes a span when it closes, so the child stands first in the list).
    A gap the child covers in part (74..100 ms) takes the parent's name —
    on the chip, every gap PR 24 saw. Preferring the deepest covering
    span is an edit to `xplane.idle_gaps`, for a `benchmark` issue."""
    ms = 1_000_000
    host = [["camera_readback", 14 * ms, 21 * ms],
            ["dispatch", 10 * ms, 22 * ms],
            ["fetch.copy", 80 * ms, 92 * ms], ["fetch", 60 * ms, 96 * ms]]
    gaps = dict(xplane.Trace(FIX["events"]).idle_gaps(host, 3))
    assert gaps["host:camera_readback"] == pytest.approx(0.005)
    assert gaps["host:fetch"] == pytest.approx(0.026)
