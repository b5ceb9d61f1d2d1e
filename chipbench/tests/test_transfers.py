"""The transfers' readers of PR 43 (`d2h_GB_per_s`, `d2h_beside_share`,
`host_fault_MB_per_frame`, `launch_held_ms`) on a hand-made window whose
answers are known, on spans from before their attributes, and in a traced
rehearsal of every cell each one lists: a reader that can give nothing in a
cell it lists is listed wrongly."""

import os

import pytest

from chipbench import harness, rehearse
from chipbench.tests.test_loop_spans import OLD, SPANS, span

NAMES = ("d2h_GB_per_s", "d2h_beside_share", "host_fault_MB_per_frame",
         "launch_held_ms")
FIVE = ["gs512-insitu", "gs512-4rank-insitu", "shm512-ingest",
        "vortex256-4rank-insitu", "gs1024-4rank-insitu"]


def readers() -> dict:
    return {m.NAME: m for m in harness.load_layers() if m.NAME in NAMES}


def copy(ts, dur, frame, nbytes, waited, beside0, beside1, **more):
    return span("fetch.copy", ts, dur, frame, parent="fetch", bytes=nbytes,
                waited=waited, beside0=beside0, beside1=beside1, **more)


def iteration(t, frame, launch, fetch_spans, touched_frame, page=4096,
              **dispatch):
    """One iteration of the loop's thread from ms `t`: the launch of
    `frame` (`launch` = ms inside steer, sim, dispatch), the fetch of the
    frame before, `sinks` at t + 40, and the second `upkeep` with the
    iteration's faults."""
    steer, sim, disp = launch
    return [
        span("steer", t, steer, frame, msgs=0, prev_ready=False),
        span("sim", t + steer, sim, frame, prev_ready=False),
        span("dispatch", t + steer + sim, disp, frame, steer_seq=0,
             **{"prev_ready": False, "upload_busy": False, **dispatch}),
        span("upkeep", t + 18, 0.5, frame),
        span("release", t + 19, 1, frame - 1, bytes=1000, rss_pages=-50,
             minflt=0),
        *fetch_spans,
        span("sinks", t + 40, 2, frame - 1, steer_seq=0),
        span("release", t + 43, 1, frame - 1, device=True, rss_pages=4,
             minflt=1),
        span("upkeep", t + 45, 0.5, frame, touched_frame=touched_frame,
             rss_pages_frame=0, minflt_frame=touched_frame // 100,
             page=page),
    ]


def window():
    """Four iterations of 50 ms. Frame 0 (fetched in iteration 1): waited,
    2 MB on two shards from the end of `fetch.ready` (ms 70) to ms 74,
    beside a newer frame throughout; frame 1: waited, 2 MB in 2 ms, alone;
    frame 2: waited, beside at its start only; frame -1: not waited for."""
    mb = 1_000_000
    return [
        *iteration(0, 0, (1, 2, 3), [
            span("fetch.ready", 20, 0.1, -1, parent="fetch"),
            copy(20.2, 0.1, -1, 2 * mb, False, True, True),
            span("fetch", 20, 15, -1, steer_seq=0, rss_pages=10)], 100),
        *iteration(50, 1, (1, 2, 3), [
            span("fetch.ready", 60, 10, 0, parent="fetch"),
            copy(70, 3, 0, mb, True, True, True, shard=0),
            copy(73, 1, 0, mb, True, True, True, shard=1),
            span("fetch.concat", 74, 2, 0, parent="fetch", bytes=2 * mb,
                 fresh=False, kmajor=True, rss_pages=40),
            span("fetch", 60, 18, 0, steer_seq=0, rss_pages=500)], 1000),
        *iteration(100, 2, (1, 2, 13), [
            span("fetch.ready", 120, 5, 1, parent="fetch"),
            copy(125, 2, 1, 2 * mb, True, False, False),
            span("fetch", 120, 10, 1, steer_seq=0, rss_pages=20)], 200,
            prev_ready=True),
        *iteration(150, 3, (1, 2, 3), [
            span("fetch.ready", 170, 5, 2, parent="fetch"),
            copy(175, 4, 2, 2 * mb, True, True, False),
            span("fetch", 170, 10, 2, steer_seq=0, rss_pages=30)], 300),
    ]


def ctx(spans) -> dict:
    return {"spans": spans, "frames": 4, "window_s": 0.200, "steers": [],
            "workload": "gs512-insitu"}


WANT = {
    # 6 MB in 4 + 2 + 4 ms
    "d2h_GB_per_s": 6e6 / 0.010 / 1e9,
    "d2h_beside_share": 100.0 / 3,
    "host_fault_MB_per_frame": 1600 * 4096 / 1e6 / 4,
    # launches of 6, 6, 16, 6 ms over a 10th percentile of 6
    "launch_held_ms": 2.5,
}


def test_the_new_readers_are_entries_saying_the_same():
    entries = {m["name"]: m for m in harness.load_json(
        harness.ROOT, "BENCHMARK.json")["per_layer"]}
    assert set(readers()) == set(NAMES)
    assert list(entries)[-4:] == list(NAMES)        # appended, in order
    for name, m in readers().items():
        e = entries[name]
        assert (e["unit"], e["layer"], e["moves"], e["source"]) == (
            m.UNIT, m.LAYER, m.MOVES, "program_span")
        assert e.get("workloads", "all") == m.CELLS
    assert readers()["d2h_GB_per_s"].CELLS == FIVE == \
        readers()["d2h_beside_share"].CELLS


@pytest.mark.parametrize("name", NAMES)
def test_reader_on_the_hand_made_window(name, capsys):
    assert readers()[name].read(ctx(window())) == pytest.approx(WANT[name])
    assert "MISSING SOURCE" not in capsys.readouterr().err


@pytest.mark.parametrize("name", NAMES)
def test_reader_on_spans_from_before_this_pr(name, capsys):
    """PR 24's spans (no `thread`): nothing, with the reason. PR 39's (a
    `thread`, none of the new attributes): the three that read a new
    attribute say it is missing; `launch_held_ms` reads spans that were
    there and gives its number. No span at all: nothing, not a word."""
    assert readers()[name].read(ctx(OLD["spans"])) is None
    assert "MISSING SOURCE" in capsys.readouterr().err
    got = readers()[name].read(dict(ctx(SPANS), frames=2, window_s=0.1))
    if name == "launch_held_ms":
        assert got == pytest.approx(1.0)        # launches of 7 and 9 ms
        capsys.readouterr()
    else:
        assert got is None
        assert "MISSING SOURCE" in capsys.readouterr().err
    assert readers()[name].read(ctx([])) is None
    assert capsys.readouterr().err == ""


def test_the_rate_ignores_frames_nobody_waited_for(capsys):
    """Frame -1 had landed before the host asked: its 2 MB in 0.1 ms would
    read 20 GB/s. The classes print with their counts."""
    assert readers()["d2h_GB_per_s"].read(ctx(window())) == \
        pytest.approx(0.6)
    err = capsys.readouterr().err
    assert "3 of 4 fetched frames were waited for" in err
    assert "alone: 1.000 GB/s x 1 (2.00 ms a frame)" in err
    assert "beside: 0.500 GB/s x 1 (4.00 ms a frame)" in err
    assert "part: 0.500 GB/s x 1" in err
    none = [dict(e, attrs=dict(e["attrs"], waited=False))
            if e["name"] == "fetch.copy" else e for e in window()]
    for name in ("d2h_GB_per_s", "d2h_beside_share"):
        assert readers()[name].read(ctx(none)) is None
    assert "MISSING SOURCE" not in capsys.readouterr().err


def test_identical_launches_are_not_held_and_the_classes_print(capsys):
    calm = [dict(e, dur=0.003, attrs=dict(e["attrs"], prev_ready=False))
            if e["name"] == "dispatch" else e for e in window()]
    assert readers()["launch_held_ms"].read(ctx(calm)) == pytest.approx(0.0)
    assert "(False, False): 0.000 ms x 4" in capsys.readouterr().err
    assert readers()["launch_held_ms"].read(ctx(window())) == \
        pytest.approx(2.5)
    err = capsys.readouterr().err
    assert "(False, False): 0.000 ms x 3" in err
    assert "(True, False): 10.000 ms x 1" in err
    assert "grew most: dispatch" in err


def test_a_slow_delivery_names_the_span_that_holds_its_excess(capsys):
    """Six iterations of 50 ms and one of 80 whose `sim` span took 30 ms
    more: the delivery after it is over 1.3 x the median, and `sim` is
    named first with the excess."""
    spans = []
    for i, extra in enumerate((0, 0, 0, 30, 0, 0, 0)):
        t = 50 * i + (30 if i > 3 else 0)
        it = iteration(t, i, (1, 2 + extra, 3), [], 0)
        for e in it:
            if e["name"] not in ("steer", "sim", "dispatch"):
                e["ts"] += extra / 1e3
        spans += it
    readers()["launch_held_ms"].read(dict(ctx(spans), frames=7,
                                          window_s=0.38))
    err = capsys.readouterr().err
    assert "1 of 6 deliveries took over 1.3 x the median interval 50.00 ms" \
        in err
    assert "excess by span" in err and "sim +30.00" in err


def test_the_touched_pages_use_the_page_size_of_the_run(capsys):
    big = [dict(e, attrs=dict(e["attrs"], page=16384))
           if "touched_frame" in (e.get("attrs") or {}) else e
           for e in window()]
    assert readers()["host_fault_MB_per_frame"].read(ctx(big)) == \
        pytest.approx(4 * WANT["host_fault_MB_per_frame"])
    capsys.readouterr()
    readers()["host_fault_MB_per_frame"].read(ctx(window()))
    err = capsys.readouterr().err
    # of 1600 pages: the fetch spans grew by 560 (40 of it in a concat),
    # the release spans by 4 x 4 (the -50 of an unmap is no growth)
    assert "fetch 0.532 + fetch.concat 0.041 + release 0.016 + rest 1.049" \
        in err
    assert "minor faults a frame 4.0 (x page = 0.016 MB)" in err
    blind = [dict(e, attrs={k: v for k, v in e["attrs"].items()
                            if k != "minflt_frame"})
             if e["name"] == "upkeep" and "attrs" in e else e
             for e in window()]
    assert readers()["host_fault_MB_per_frame"].read(ctx(blind)) == \
        pytest.approx(WANT["host_fault_MB_per_frame"])
    assert "this kernel counts no page fault" in capsys.readouterr().err


# ----------------------------------------------------- the traced rehearsals

REHEARSALS = {
    # cell name -> (home under rehearsal/, configuration, traffic)
    "gs512-insitu": ("", "tiny-1rank", "insitu10-steer"),
    "gs128-insitu": ("", "tiny-1rank", "insitu10-steer"),
    "gs512-4rank-insitu": ("", "tiny-4rank", "insitu10-steer"),
    "shm512-ingest": ("shm", "tiny-shmring", "ingest-steer"),
    "vortex256-4rank-insitu": ("vortex", "tiny-vortex-4rank",
                               "insitu10-steer"),
    "gs1024-4rank-insitu": ("gs1024", "tiny-gsblocks-4rank",
                            "insitu10-steer"),
}


def test_every_cell_of_the_benchmark_is_rehearsed():
    cells = {w["name"] for w in harness.load_json(
        harness.ROOT, "BENCHMARK.json")["workloads"]}
    assert cells == set(REHEARSALS) and set(FIVE) < cells


@pytest.mark.parametrize("name", sorted(REHEARSALS))
def test_a_traced_rehearsal_gives_a_number_in_every_listed_cell(name, capfd):
    """Under the cell's own name, at its rehearsal size: every one of the
    four that lists the cell is in the result (never nothing), with its
    class split on stderr."""
    home, config, traffic = REHEARSALS[name]
    cell = harness.find_files(
        {"name": name, "config": config, "traffic": traffic},
        home=os.path.join(harness.HERE, "rehearsal", home))
    cell = dict(cell, chips=cell["config_file"]["chips"])
    res = rehearse.rehearse(cell, 4_300_000_019, 0.5, True)
    assert res["correct"]
    got = {k: v[0] for k, v in res["per_layer"].items()}
    want = {n for n, m in readers().items()
            if m.CELLS == "all" or name in m.CELLS}
    assert want == set(NAMES) - (set() if name in FIVE else {
        "d2h_GB_per_s", "d2h_beside_share"})
    assert want <= set(got), want - set(got)
    assert got["host_fault_MB_per_frame"] >= 0.0
    assert got["launch_held_ms"] >= 0.0
    if name in FIVE:
        assert got["d2h_GB_per_s"] > 0.0
        assert 0.0 <= got["d2h_beside_share"] <= 100.0
    err = capfd.readouterr().err
    assert "MISSING SOURCE: no `fetch.copy`" not in err
    assert "MISSING SOURCE: no `upkeep`" not in err
    assert "host_fault_MB_per_frame" in err and "launch_held_ms: over" in err
