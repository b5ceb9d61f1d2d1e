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
    "camera_readback_ms": 5.5,          # has no shard and does not count
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


@pytest.mark.parametrize("name,want", [
    ("march_device_ms", 0.0), ("fold_device_ms", 0.0),
    ("composite_device_ms", 0.0), ("step_unscoped_share", 100.0)])
def test_a_program_without_a_table_joins_nothing(name, want, monkeypatch,
                                                 capsys):
    """A commit before PR 24 keeps no table: the readers do not raise,
    every op of the step program is unexplained, and stderr says that
    the number stands for a missing source."""
    got = readers()[name].read(ctx(table=({}, {}), monkeypatch=monkeypatch))
    assert got == pytest.approx(want)
    assert "MISSING SOURCE" in capsys.readouterr().err


NAMES = {"fetch_ready_ms": "fetch.ready", "fetch_copy_ms": "fetch.copy",
         "camera_readback_ms": "camera_readback"}


@pytest.mark.parametrize("name", sorted(NAMES))
def test_a_program_without_the_span_reads_zero(name, capsys):
    """A commit before PR 24 has `fetch`, `sim`, `dispatch` only: run.py
    ends a run that leaves a declared metric out, so the sum over no span
    is 0, not nothing — and stderr says so, since 0 is the best a `lower`
    metric can read; with no span at all there is nothing to read, and a
    span that is there is read without a word."""
    old = [e for e in FIX["spans"] if "." not in e["name"]
           and e["name"] != "camera_readback"]
    c = dict(ctx(), spans=old)
    assert readers()[name].read(c) == 0.0
    err = capsys.readouterr().err
    assert "MISSING SOURCE" in err and NAMES[name] in err
    assert readers()[name].read(dict(c, spans=[])) is None
    assert readers()[name].read(ctx()) > 0.0
    assert capsys.readouterr().err == ""


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
