"""`runtime/hostheap.py` and the session's use of it (PR 45): which frames
make a session tell glibc to keep the process's large blocks, that glibc
then does, that asking twice or without a glibc is harmless, and the
counters. Every case runs in a child process: engaging is for a whole
process and for good, and the test runner's own heap stays as it is. What
it buys on a chip's host is the chip's to say (PERF.md, PR 45)."""

import json
import os
import platform
import subprocess
import sys

import pytest

from scenery_insitu_tpu.runtime import hostheap

F32 = 4

_CHILD = r"""
import json, sys
plan = json.loads(sys.argv[1])
from scenery_insitu_tpu import obs
from scenery_insitu_tpu.config import FrameworkConfig
from scenery_insitu_tpu.core.vdi import VDI
from scenery_insitu_tpu.runtime import hostheap
from scenery_insitu_tpu.runtime.session import InSituSession


class Shard:
    def __init__(self, nbytes):
        self.data = self
        self.nbytes = nbytes


class Leaf:
    '''What `_start_host_copy` asks of a frame's leaf, at a cell's full
    size and without its bytes: ``mine`` lists the shards this process
    holds of a leaf that other processes hold the rest of.'''
    def __init__(self, nbytes, mine=None):
        self.nbytes = nbytes
        self.is_fully_addressable = mine is None
        self.addressable_shards = [Shard(n) for n in mine or ()]
        self.asked = 0

    def copy_to_host_async(self):
        self.asked += 1


said = []
cfg = FrameworkConfig().with_overrides(
    "render.width=32", "render.height=24", "render.max_steps=24",
    "vdi.max_supersegments=6", "vdi.adaptive_iters=2",
    "composite.max_output_supersegments=8", "composite.adaptive_iters=2",
    "sim.grid=[16,16,16]", "sim.steps_per_frame=2",
    "obs.enabled=" + str(plan["enabled"]).lower())
sess = InSituSession(cfg, sinks=[lambda i, p: None] if plan["sink"] else [],
                     log=said.append)
if plan["leaves"]:
    frame = VDI(*[Leaf(*leaf) for leaf in plan["leaves"]])
    for _ in range(3):      # decided by the first frame, asked of all
        sess._start_host_copy(frame)
    assert frame.color.asked == frame.depth.asked == 3
else:
    sess.run(3, fetch=plan["sink"])
print(json.dumps({
    "counters": sess.obs.counters, "kept": hostheap._kept,
    "said": [s for s in said if "hostheap" in s],
    "summary": sess.obs.summary()["counters"],
    "counted": [e["name"] for e in sess.obs.events
                if e["type"] == "counter" and "host_heap" in e["name"]],
    "ledger": [r["component"] for r in obs.ledger()]}))
"""


def _child(code: str, *args: str) -> dict:
    p = subprocess.run([sys.executable, "-c", code, *args],
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _session(leaves, sink=True, enabled=False) -> dict:
    return _child(_CHILD, json.dumps(
        {"leaves": leaves, "sink": sink, "enabled": enabled}))


def _vdi(k, side, ranks=1, processes=1):
    """The leaves of a cell's frame, f32 colour [k, 4, side, side] and
    depth [k, 2, side, side]: (bytes, None) where this process holds all
    of a leaf, else (bytes, [its own shards' bytes])."""
    leaves = []
    for channels in (4, 2):
        nbytes = k * channels * side * side * F32
        mine = None if processes == 1 else \
            [nbytes // ranks] * (ranks // processes)
        leaves.append((nbytes, mine))
    return leaves


# the seven cells' frames (PERF.md §4), then two layouts no cell has: the
# same frames on a mesh that two processes hold, where each process
# fetches its own half
FRAMES = {
    "gs128-insitu": (_vdi(16, 160), 9830400, False),
    "gs512-insitu": (_vdi(16, 640), 157286400, True),
    "shm512-ingest": (_vdi(16, 640), 157286400, True),
    "gs512-4rank-insitu": (_vdi(16, 640, ranks=4), 157286400, True),
    "vortex256-4rank-insitu": (_vdi(16, 320, ranks=4), 39321600, True),
    "gs1024-4rank-insitu": (_vdi(16, 1280, ranks=4), 629145600, True),
    "kingsnake-u8-view": (_vdi(20, 1280), 786432000, True),
    "gs512-4rank-2proc": (_vdi(16, 640, 4, 2), 78643200, True),
    "vortex256-4rank-2proc": (_vdi(16, 320, 4, 2), 19660800, False),
}


@pytest.mark.parametrize("cell", list(FRAMES))
def test_the_frame_a_session_fetches_decides(cell):
    """Engaged for a frame whose host bytes in this process reach glibc's
    ceiling for heap blocks (39 / 79 / 157 / 629 / 786 MB), not under it
    (9.8 / 19.7 MB); one line through the session's log where engaged."""
    leaves, nbytes, kept = FRAMES[cell]
    assert (nbytes >= hostheap.DEFAULT_MMAP_THRESHOLD_MAX) == kept
    got = _session(leaves)
    assert got["counters"]["host_heap_frame_bytes"] == nbytes
    assert got["counters"]["host_heap_kept"] == int(kept)
    assert got["kept"] == (True if kept else None)      # None: never asked
    assert len(got["said"]) == int(kept) and got["ledger"] == []
    if kept:
        assert "keeps its large blocks" in got["said"][0]


def test_a_session_that_fetches_nothing_leaves_the_heap_alone():
    got = _session(None, sink=False)
    assert got["kept"] is None and got["said"] == []
    assert not [c for c in got["counters"] if "host_heap" in c]


@pytest.mark.parametrize("enabled", [True, False])
@pytest.mark.parametrize("leaves,kept", [(None, 0), (_vdi(16, 640), 1)])
def test_host_heap_kept_is_counted_recorded_or_not(leaves, kept, enabled):
    """Both counters after a real tiny run with a sink (0: 147 kB a frame)
    and after a 157 MB frame (1), in the recorder's counters and its
    summary; their events only in a recorded run, once a session."""
    got = _session(leaves, enabled=enabled)
    for counters in (got["counters"], got["summary"]):
        assert counters["host_heap_kept"] == kept
        assert counters["host_heap_frame_bytes"] == (
            157286400 if kept else 8 * 6 * 24 * 32 * F32)
    assert got["counted"] == (
        ["host_heap_kept", "host_heap_frame_bytes"] if enabled else [])


_TWICE = r"""
import ctypes, json, sys
from scenery_insitu_tpu import obs
from scenery_insitu_tpu.runtime import hostheap

calls, said = [], []
if sys.argv[1] == "fake":
    def fake(say):
        return lambda param, value: calls.append((param, value)) or 1
    hostheap._mallopt = fake
elif sys.argv[1] == "no-libc":
    def gone(name):
        raise OSError(name + ": cannot open shared object file")
    ctypes.CDLL = gone
elif sys.argv[1] == "no-mallopt":
    ctypes.CDLL = lambda name: object()
found = [hostheap.keep_large_blocks(said.append) for _ in range(3)]
found.append(hostheap.keep_large_blocks())
print(json.dumps({"found": found, "calls": calls, "said": said,
                  "ledger": obs.ledger()}))
"""


def test_keep_large_blocks_acts_once():
    """Four calls: the three options set once (M_MMAP_MAX 0,
    M_TRIM_THRESHOLD -1 = never, M_ARENA_MAX 1), one line, True each time;
    and with the real glibc where there is one."""
    got = _child(_TWICE, "fake")
    assert got["found"] == [True] * 4 and len(got["said"]) == 1
    assert sorted(map(tuple, got["calls"])) == [
        (-8, 1), (-4, 0), (-1, -1)]
    if platform.libc_ver()[0] == "glibc":
        real = _child(_TWICE, "real")
        assert real["found"] == [True] * 4 and real["ledger"] == []
        assert len(real["said"]) == 1 and "[1, 1, 1]" in real["said"][0]


@pytest.mark.parametrize("how", ["no-libc", "no-mallopt"])
def test_without_glibc_it_does_nothing_and_says_why(how):
    got = _child(_TWICE, how)
    assert got["found"] == [False] * 4 and len(got["said"]) == 1
    assert "mallopt is not there" in got["said"][0]
    assert [(r["component"], r["from"], r["to"], r["count"])
            for r in got["ledger"]] == [
                ("host.heap", "keep_large_blocks", "libc_defaults", 1)]
    assert ("OSError" if how == "no-libc" else "AttributeError") \
        in got["ledger"][0]["reason"]


_BLOCKS = r"""
import json, threading
import numpy as np
from scenery_insitu_tpu.obs.hostmem import PAGE, HostPages
from scenery_insitu_tpu.runtime import hostheap

pages = HostPages()


def grown(nbytes=64 << 20):
    '''MB the resident set grows by while a fresh block of ``nbytes`` is
    taken and every page of it written; the block is let go after.'''
    before = pages.read()
    block = np.empty(nbytes, np.uint8)
    block[::PAGE] = 1
    return pages.since(before)["rss_pages"] * PAGE / 1e6


out = {"before": [grown() for _ in range(3)]}
out["kept"] = hostheap.keep_large_blocks()
out["after"] = [grown() for _ in range(4)]
out["larger"] = [grown(157286400) for _ in range(3)]


def other():
    out["thread"] = [grown() for _ in range(3)]


t = threading.Thread(target=other)
t.start()
t.join(60)
print(json.dumps(out))
"""


@pytest.mark.skipif(platform.libc_ver()[0] != "glibc",
                    reason="no glibc: mallopt has nothing to tell")
@pytest.mark.skipif(not os.path.exists("/proc/self/statm"),
                    reason="no /proc/self/statm: the resident set cannot "
                           "be read")
def test_glibc_keeps_a_64_MB_block_once_told_late():
    """On the first thread, with numpy imported and blocks already mapped
    and unmapped: before engaging every 64 MB block lands on fresh pages,
    after it only the first does (the heap grows once), and a larger one
    pays the difference once; a thread born afterwards shares that heap."""
    got = _child(_BLOCKS)
    mb = (64 << 20) / 1e6
    assert got["kept"] is True
    assert all(abs(g - mb) < 4 for g in got["before"]), got
    assert got["after"][0] > mb - 4, got
    assert all(abs(g) < 4 for g in got["after"][1:]), got
    assert 157.3 - mb - 4 < got["larger"][0] < 157.3 + 4, got
    assert all(abs(g) < 4 for g in got["larger"][1:] + got["thread"]), got
