"""`python -m scenery_insitu_tpu.obs.transfer_probe` (PR 43; the `late`
children PR 45) at a megabyte on the CPU and on the 4-device CPU mesh: the
command as the chip runs it, children and all; every row of every table is
there with a number in it. What the numbers are is the chip's to say, not
this file's."""

import json
import subprocess
import sys

import pytest

from scenery_insitu_tpu.obs import transfer_probe as tp
from scenery_insitu_tpu.utils.backend import virtual_mesh_env

STATES = ("idle",) + tp.FILLERS


@pytest.fixture(scope="module")
def probed():
    """{devices: (the printed report, the JSON)} of one run each."""
    found = {}
    for devices in (1, 4):
        p = subprocess.run(
            [sys.executable, "-m", "scenery_insitu_tpu.obs.transfer_probe",
             "--bytes", "1048576", "--devices", str(devices), "--repeats",
             "2", "--filler-ms", "3", "--json"],
            env=virtual_mesh_env(4), capture_output=True, text=True,
            timeout=600)
        assert p.returncode == 0, p.stderr[-2000:]
        lines = p.stdout.strip().splitlines()
        found[devices] = ("\n".join(lines[:-1]), json.loads(lines[-1]))
    return found


def _rows_transfer(text, res):
    want = [(p, b) for p in tp.PIECES for b in STATES]
    assert [(r["pieces"], r["beside"]) for r in res["transfer"]] == want
    assert [(r["pieces"], r["beside"]) for r in res["mallopt"]] == \
        [(1, b) for b in STATES]
    for r in res["transfer"] + res["mallopt"]:
        assert r["ms"] > 0 and r["GB/s"] > 0
        assert isinstance(r["fresh_MB"], float)
        assert ("minflt" in r) == res["counts_faults"]
        assert r["free_fresh_MB"] <= r["fresh_MB"] + 64
        assert r["free_dev_ms"] >= 0 and r["free_host_ms"] >= 0
        assert ("filler_left" in r) == (r["beside"] != "idle")
        assert len(r["trials"]) == 2
    assert "mallopt" in text and "free_host_ms" in text


def _rows_late(text, res):
    """The children that tell glibc late (runtime/hostheap.py): one
    default row before the call, the whole-frame rows after it, on the
    first thread and on another."""
    assert tp.LATE == ("late", "late-thread")
    for name in tp.LATE:
        assert [(r["pieces"], r["beside"])
                for r in res[name + ":before"]] == [(1, "idle")]
        assert [(r["pieces"], r["beside"]) for r in res[name]] == \
            [(1, b) for b in STATES]
        for r in res[name + ":before"] + res[name]:
            assert r["ms"] > 0 and r["GB/s"] > 0 and len(r["trials"]) == 2
            assert isinstance(r["fresh_MB"], float)
        firsts = [line.split()[:1] for line in text.splitlines()]
        assert firsts.count([name + ":before"]) == 1
        assert firsts.count([name]) == len(STATES)


def _rows_held(text, res):
    assert [(r["pieces"], r["beside"], r["held"]) for r in res["held"]] == [
        (p, b, h) for p in tp.PIECES for b in ("idle", "stream")
        for h in ("call", "put")]
    reads = res["devices"] * len(tp.CHANNELS)
    for r in res["held"]:
        assert 0 <= r["ret_ms"] <= r["done_ms"] <= r["ms"]
        landed, of = map(int, r["landed"].split("/"))
        assert 0 <= landed <= of == r["pieces"] * reads
    assert "a launch made with the transfer in flight" in text


def _rows_shard_ends(text, res):
    if res["devices"] == 1:
        assert "shard_ends" not in res and "shard_ends" not in text
        return
    assert [r["beside"] for r in res["shard_ends"]] == [
        b for b in STATES for _ in range(2)]
    for r in res["shard_ends"]:
        assert len(r["ends_ms"]) == 4 and min(r["ends_ms"]) > 0


def _rows_destinations(text, res):
    assert [r["beside"] for r in res["pinned_host"]] == list(STATES)
    for r in res["pinned_host"]:        # a number, or the runtime's reason
        assert ("unsupported" in r) != ("GB/s" in r)
        assert r.get("unsupported", "x") != ""
    assert res["thp"] and "thp " + res["thp"] in text
    assert res["bytes"] == 16 * 24 * res["side"] ** 2 <= 1048576
    assert res["platform"] == "cpu"


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("rows", ["transfer", "held", "shard_ends",
                                  "destinations", "late"])
def test_the_probes_table_has_every_row(probed, rows, devices):
    globals()["_rows_" + rows](*probed[devices])


def test_the_probe_tells_glibc_as_the_session_does():
    """One definition: the probe's children call the program's own."""
    from scenery_insitu_tpu.runtime import hostheap
    assert tp.keep_large_blocks is hostheap.keep_large_blocks


@pytest.mark.parametrize("nbytes,side", [(157286400, 640),
                                         (629145600, 1280), (1048576, 48)])
def test_the_frames_of_the_cells_come_out_whole(nbytes, side):
    assert tp.frame_side(nbytes) == side
    assert (16 * 24 * side * side == nbytes) == (side != 48)
