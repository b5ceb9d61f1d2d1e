"""The host-side readers of PR 39 on a hand-made window: two iterations of
the loop's thread, 50 ms each, with an upload beside them on the uploader's
thread and one camera message followed from its drain to its sink. Every
answer is known by hand. On a span list from before `thread` (the fixture of
PR 24) every one of them reads nothing and says why; and the traced
rehearsals print every one of them that their cell lists."""

import os

import pytest

from chipbench import harness, rehearse

OLD = harness.load_json(harness.HERE, "fixtures", "scopes_small.json")
EPOCH = 1000.0      # the recorder's: `ts` is relative to it, `t_drain` not


def span(name, ts_ms, dur_ms, frame, thread="MainThread", parent=None,
         **attrs):
    e = {"type": "span", "name": name, "rank": 0, "ts": ts_ms / 1e3,
         "dur": dur_ms / 1e3, "depth": 0 if parent is None else 1,
         "thread": thread, "frame": frame}
    if parent is not None:
        e["parent"] = parent
    if attrs:
        e["attrs"] = attrs
    return e


SPANS = [
    # iteration 0: launches frame 0 from message 1, retires frame -1
    span("steer", 0, 1, 0, msgs=1, seq=1, t_drain=EPOCH + 0.0002),
    span("ingest.wait", 1.2, 1, 0, parent="sim"),
    span("sim", 1, 2, 0, kind="external"),
    span("dispatch", 3, 4, 0, steer_seq=1, upload_busy=True,
         prev_ready=False),
    span("upkeep", 7, 0.5, 0),
    span("host_copy.start", 8, 1, 0, bytes=4096),
    span("release", 9, 2, -1, bytes=4096),
    span("fetch.ready", 11, 12, -1, parent="fetch"),
    span("fetch.concat", 24, 3, -1, parent="fetch", bytes=4096, fresh=True),
    span("fetch", 11, 20, -1, steer_seq=0),
    span("sinks", 31, 3, -1, steer_seq=0),
    span("upkeep", 34, 0.5, 0),
    # beside the loop, on a thread of its own: no part of the accounting
    span("ingest.upload", 2, 30, 0, thread="shm-uploader", bytes=1 << 20),
    # iteration 1: launches frame 1, retires frame 0 (the message's pixels)
    span("steer", 50, 1, 1, msgs=0),
    span("sim", 51, 2, 1, kind="external"),
    span("dispatch", 53, 6, 1, steer_seq=1, upload_busy=False,
         prev_ready=True),
    span("host_copy.start", 60, 1, 1, bytes=4096),
    span("release", 61.5, 2, 0, parent="upkeep", bytes=4096),
    span("upkeep", 61, 3, 1),
    span("fetch.ready", 64, 10, 0, parent="fetch"),
    span("fetch.concat", 75, 3, 0, parent="fetch", bytes=4096, fresh=False),
    span("fetch", 64, 20, 0, steer_seq=1),
    span("sinks", 84, 3, 0, steer_seq=1),
    span("upkeep", 87, 0.5, 1),
]
# the viewer handed message 1 over 0.3 ms before the window's first `steer`
# span opened and saw its pixels 0.1 ms into frame 0's `sinks` span
STEERS = [(EPOCH - 0.0003, EPOCH + 0.0841, 2)]
WANT = {
    "host_unspanned_ms": 50 - (34 + 36.5) / 2,
    "host_serial_ms": 50 - (12 + 10) / 2 - 1 / 2,
    "steer_ms": 1.0,
    "loop_upkeep_ms": (0.5 + 0.5 + 3 + 0.5) / 2 + 2 / 2,
    "host_copy_start_ms": 1.0,
    "steer_queue_ms": 0.5,              # sent -> t_drain
    "steer_frame_ms": 84.0,             # `steer` start -> `sinks` start
    "launch_prev_ready_share": 50.0,
    "launch_upload_busy_share": 50.0,
    "step_dispatch_busy_ms": 5.0,       # both launches are marked: 4 and 6
    "fetch_fresh_share": 50.0,
}
PER_CELL = {"launch_upload_busy_share", "step_dispatch_busy_ms",
            "fetch_fresh_share"}


def ctx(spans=SPANS, steers=STEERS) -> dict:
    return {"spans": spans, "frames": 2, "window_s": 0.100,
            "steers": steers}


def readers() -> dict:
    return {m.NAME: m for m in harness.load_layers() if m.NAME in WANT}


def test_every_new_reader_has_its_answer_and_its_entry():
    assert set(readers()) == set(WANT)
    entries = {m["name"]: m for m in harness.load_json(
        harness.ROOT, "BENCHMARK.json")["per_layer"]}
    for name, m in readers().items():
        assert m.SOURCE == entries[name]["source"] == "program_span"
        assert ("workloads" in entries[name]) == (name in PER_CELL)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_the_hand_made_window(name, capsys):
    assert readers()[name].read(ctx()) == pytest.approx(WANT[name])
    assert "MISSING SOURCE" not in capsys.readouterr().err


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_spans_from_before_this_pr(name, capsys):
    """Today's spans (no `thread`, none of the new names or attributes):
    nothing, with the reason on stderr, and no crash; with no span at all,
    nothing and not a word."""
    assert readers()[name].read(ctx(OLD["spans"])) is None
    assert "MISSING SOURCE" in capsys.readouterr().err
    assert readers()[name].read(ctx([])) is None
    assert capsys.readouterr().err == ""


def test_the_loops_thread_is_the_thread_of_its_launches():
    from chipbench import loop_spans

    got = loop_spans.loop(ctx())
    assert {e["thread"] for e in got} == {"MainThread"}
    assert len(got) == len(SPANS) - 1           # all but the upload
    # a program that gives the launches a thread of their own is followed
    moved = [dict(e, thread="frame-loop") if e["thread"] == "MainThread"
             else e for e in SPANS]
    assert readers()["steer_ms"].read(ctx(moved)) == 1.0


def test_a_launch_class_nobody_is_in(capsys):
    """No launch of the window marked busy: the share reads 0, which the
    spans show, and the mean of no launch is nothing."""
    calm = [dict(e, attrs=dict(e["attrs"], upload_busy=False,
                               prev_ready=False))
            if e["name"] == "dispatch" else e for e in SPANS]
    assert readers()["launch_upload_busy_share"].read(ctx(calm)) == 0.0
    assert readers()["launch_prev_ready_share"].read(ctx(calm)) == 0.0
    assert readers()["step_dispatch_busy_ms"].read(ctx(calm)) is None
    err = capsys.readouterr().err
    assert "(False, False): 5.000 ms x 2" in err
    # the window's one upload began before either launch: none met it
    assert "an upload began" not in err
    begun = calm + [span("ingest.upload", 54, 30, 1, thread="shm-uploader",
                         bytes=1 << 20)]
    assert readers()["step_dispatch_busy_ms"].read(ctx(begun)) is None
    assert "an upload began: 1, 6.000 ms" in capsys.readouterr().err


def test_steer_parts_that_do_not_add_up_are_named(capsys):
    """A message whose queue and frame parts miss the viewer's own time by
    more than 1 ms is named on stderr (and still read); one drained before
    the window has no span here and is passed over."""
    late = [(EPOCH - 0.0003, EPOCH + 0.0891, 2)]     # 5 ms after `sinks`
    assert readers()["steer_frame_ms"].read(ctx(steers=late)) == \
        pytest.approx(84.0)
    err = capsys.readouterr().err
    assert "do not add up" in err and "message 1" in err
    assert readers()["steer_queue_ms"].read(ctx()) == pytest.approx(0.5)
    assert "do not add up" not in capsys.readouterr().err
    before = [(EPOCH - 0.2, EPOCH - 0.1, 2)]
    assert readers()["steer_queue_ms"].read(ctx(steers=before)) is None


# ----------------------------------------------------- the traced rehearsals

REHEARSALS = {
    # cell name -> (home under rehearsal/, configuration, traffic)
    "rehearsal-tiny-1rank": ("", "tiny-1rank", "insitu10-steer"),
    "gs512-4rank-insitu": ("", "tiny-4rank", "insitu10-steer"),
    "shm512-ingest": ("shm", "tiny-shmring", "ingest-steer"),
    "vortex256-4rank-insitu": ("vortex", "tiny-vortex-4rank",
                               "insitu10-steer"),
}


@pytest.mark.parametrize("name", sorted(REHEARSALS))
def test_a_traced_rehearsal_prints_the_new_metrics(name, capfd):
    """Under a cell's own name, so that the readers' `CELLS` let them run:
    every new metric that lists "all" or this cell is in the result, the
    loop's accounting adds up to the interval, and every answered message
    of the window is followed with parts that add up."""
    home, config, traffic = REHEARSALS[name]
    cell = harness.find_files(
        {"name": name, "config": config, "traffic": traffic},
        home=os.path.join(harness.HERE, "rehearsal", home))
    cell = dict(cell, chips=cell["config_file"]["chips"])
    res = rehearse.rehearse(cell, 3_000_000_019, 0.5, True)
    assert res["correct"]
    got = {k: v[0] for k, v in res["per_layer"].items()}
    want = {n for n, m in readers().items()
            if m.CELLS == "all" or name in m.CELLS}
    assert want == (set(WANT) - PER_CELL) | {
        n for n in PER_CELL if name in readers()[n].CELLS}
    if "step_dispatch_busy_ms" in want - set(got):
        # a CPU's uploads are short: no launch of the window met one
        assert got["launch_upload_busy_share"] == \
            got["launch_prev_ready_share"] == 0.0
        want.discard("step_dispatch_busy_ms")
    assert want <= set(got), want - set(got)
    interval = res["window_s"] / res["attempted"] * 1e3
    assert 0.0 <= got["host_unspanned_ms"] < 0.2 * interval
    assert got["host_unspanned_ms"] < got["dispatch_ms"] < interval
    assert 0.0 < got["host_serial_ms"] < interval
    for part in ("steer_ms", "loop_upkeep_ms", "host_copy_start_ms",
                 "steer_queue_ms", "steer_frame_ms"):
        assert got[part] > 0.0, part
    err = capfd.readouterr().err
    assert "MISSING SOURCE: the program's spans" not in err
    assert "answered messages followed from drain to sink" in err
    assert "do not add up" not in err
