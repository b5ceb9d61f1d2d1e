"""The shm source's upload beside the frame loop (`ShmVolumeSource`): the
fields a lockstep producer publishes reach the loop bit-equal, once each, in
order, whether the producer is faster or slower than the loop; a frame
rendered from a shm-fed field is the frame rendered from the same field fed
directly; the spans and counters exist with `obs.enabled` and cost nothing
without; `close()` joins and detaches; a producer whose parent is killed
goes with it and leaves no segment. Small grids on the CPU; every test
under its own time limit."""

import contextlib
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
import uuid

import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    shutil.which("g++") is None and shutil.which("c++") is None,
    reason="no C++ toolchain")

from scenery_insitu_tpu import obs  # noqa: E402
from scenery_insitu_tpu.ingest.shm import (ShmConsumer, ShmProducer,  # noqa: E402
                                           ShmVolumeSource, channel_stats,
                                           unlink)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GRID = (12, 10, 16)
FIELDS = 24


@contextlib.contextmanager
def limit(seconds: int):
    """This test's own time limit (SIGALRM on the worker's main thread)."""
    def late(*_):
        raise TimeoutError(f"the test ran past its {seconds} s")

    before = signal.signal(signal.SIGALRM, late)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, before)


def chan() -> str:
    return f"/sitpu_ovl_{uuid.uuid4().hex[:12]}"


def field(seed: int, i: int) -> np.ndarray:
    return np.random.default_rng([seed, i]).random(GRID, np.float32)


class Lockstep(threading.Thread):
    """A producer in lockstep with its reader, as the benchmark's: field i
    is written in place into an acquired slot and goes out once the reader
    has pinned field i - 1 (`consumed_seq`, through the producer's own
    handle); `delay_s` makes it slower than the loop."""

    def __init__(self, channel, seed, count, nslots=2, delay_s=0.0):
        super().__init__(daemon=True)
        self.prod = ShmProducer(channel, GRID, nslots=nslots)
        self.seed, self.count, self.delay_s = seed, count, delay_s
        self.stop = threading.Event()
        self.start()

    def _until(self, ready) -> bool:
        while not ready(self.prod.stats()):
            if self.stop.is_set():
                return False
            time.sleep(0.0002)
        return True

    def run(self):
        for i in range(self.count):
            if not self._until(lambda st: any(
                    s["readers"] == 0 for j, s in enumerate(st["slots"])
                    if j != st["latest_slot"])):
                return
            slot = self.prod.acquire()
            np.copyto(slot, field(self.seed, i))
            del slot
            time.sleep(self.delay_s)
            if not self._until(lambda st: st["consumed_seq"] >= i):
                return
            assert self.prod.commit() == i + 1

    def end(self):
        self.stop.set()
        self.join(10)
        dropped = self.prod.frames_dropped
        self.prod.close()
        return dropped


def blocking_consume(seed: int, delay_s: float) -> list:
    """The plain path: consume (a copy), `device_put`, wait; one field
    after the other on the caller's thread."""
    import jax

    ch = chan()
    prod = Lockstep(ch, seed, FIELDS, delay_s=delay_s)
    cons = ShmConsumer(ch, GRID, timeout_ms=5000)
    got = []
    try:
        for _ in range(FIELDS):
            frame, seq = cons.latest(timeout_ms=5000)
            got.append((seq, np.asarray(jax.block_until_ready(
                jax.device_put(frame)))))
    finally:
        cons.close()
        prod.end()
    return got


@pytest.mark.parametrize("nslots", [2, 3])
@pytest.mark.parametrize("delay_s, loop_s", [(0.0, 0.004), (0.01, 0.0)],
                         ids=["fast-producer", "slow-producer"])
def test_overlapped_fields_equal_the_blocking_path(delay_s, loop_s, nslots):
    """Same fields, same order, bit-equal, none repeated, none skipped,
    none dropped by the producer: against the seeded fields and against
    what the blocking consume -> device_put path hands over."""
    with limit(120):
        ch = chan()
        prod = Lockstep(ch, 7, FIELDS, nslots=nslots, delay_s=delay_s)
        src = ShmVolumeSource(ch, GRID, timeout_ms=5000,
                              frame_timeout_ms=5000)
        got = []
        try:
            assert src.field.shape == GRID      # a look consumes nothing
            for _ in range(FIELDS):
                src.advance(1)
                got.append((src.last_seq, np.asarray(src.field)))
                time.sleep(loop_s)
        finally:
            src.close()
            dropped = prod.end()
        assert [s for s, _ in got] == list(range(1, FIELDS + 1))
        assert dropped == 0 and not src.stalled
        plain = blocking_consume(7, delay_s)
        assert [s for s, _ in plain] == [s for s, _ in got]
        for i, ((_, a), (_, b)) in enumerate(zip(got, plain)):
            assert np.array_equal(a, field(7, i)), i
            assert np.array_equal(a, b), i


class Direct:
    """The same fields fed without a channel."""

    kind = "external"

    def __init__(self, seed):
        import jax

        self._put, self.seed, self.frames = jax.device_put, seed, 0
        self.field = self._put(field(seed, 0))

    def advance(self, n):
        self.field = self._put(field(self.seed, self.frames))
        self.frames += 1


def session(sim, *overrides):
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.session import InSituSession

    frames = []
    cfg = FrameworkConfig().with_overrides(
        "slicer.engine=mxu", "vdi.adaptive_mode=temporal",
        "vdi.max_supersegments=4", "composite.max_output_supersegments=4",
        "runtime.dataset=gray_scott", "mesh.num_devices=1", *overrides)
    sess = InSituSession(cfg, sim=sim, sinks=[
        lambda i, p: frames.append((p["vdi_color"], p["vdi_depth"]))])
    return sess, frames


def test_a_frame_from_a_shm_fed_field_is_the_frame_from_that_field():
    """Through `InSituSession(cfg, sim=...)`, the normal path: frame i
    renders field i, and `InSituSession.close` ends the source."""
    with limit(300):
        ch = chan()
        prod = Lockstep(ch, 11, 6)
        src = ShmVolumeSource(ch, GRID, timeout_ms=5000,
                              frame_timeout_ms=5000)
        try:
            sess, fed = session(src)
            sess.run(5)
            assert src.last_seq == 5
            sess.close()
            assert src._thread is None and src.consumer.handle is None
        finally:
            src.close()
            assert prod.end() == 0
        direct_sess, direct = session(Direct(11))
        direct_sess.run(5)
        direct_sess.close()                     # nothing to close: no error
        assert len(fed) == len(direct) == 5
        for (c0, d0), (c1, d1) in zip(fed, direct):
            assert np.array_equal(c0, c1) and np.array_equal(d0, d1)
        assert np.asarray(fed[-1][0]).max() > 0.0


@pytest.mark.parametrize("enabled", [True, False], ids=["obs-on", "obs-off"])
def test_spans_and_counters(enabled):
    """`ingest.wait` under `sim` on the loop's thread, `ingest.upload` with
    its bytes and sequence number on the uploader's, the three counters;
    with obs off the span sites open nothing (no timer row either)."""
    with limit(300):
        ch = chan()
        prod = Lockstep(ch, 13, 5)
        src = ShmVolumeSource(ch, GRID, timeout_ms=5000,
                              frame_timeout_ms=5000)
        try:
            sess, _ = session(src, f"obs.enabled={str(enabled).lower()}")
            sess.run(4)
            prod.join(10)       # field 4, fetched ahead, was the last one
            deadline = time.monotonic() + 10
            while (sess.obs.counters.get("ingest_fields_uploaded", 0) < 5
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            src.frame_timeout_ms = 50
            sess.run(1)         # takes field 4
            sess.run(1)         # nothing newer: the frame before's field
            rec = sess.obs
        finally:
            src.close()
            prod.end()
        nbytes = int(np.prod(GRID)) * 4
        assert rec.counters["ingest_fields_uploaded"] == 5
        assert rec.counters["ingest_bytes"] == 5 * nbytes
        assert rec.counters["ingest_fields_repeated"] == 1
        assert rec.counters["ingest_stalls"] == 1
        spans = [e for e in rec.events if e["type"] == "span"]
        if not enabled:
            assert spans == []
            assert not {"ingest.wait", "ingest.upload"} & set(
                rec.timers.stats)
            return
        waits = [e for e in spans if e["name"] == "ingest.wait"]
        ups = [e for e in spans if e["name"] == "ingest.upload"]
        assert len(waits) == 6 and {e["parent"] for e in waits} == {"sim"}
        assert [e["attrs"]["seq"] for e in ups] == [1, 2, 3, 4, 5]
        assert {e["attrs"]["bytes"] for e in ups} == {nbytes}
        assert all("parent" not in e for e in ups)      # its own thread


def test_upload_busy_and_the_uploaders_own_row():
    """`upload_busy` is true from an upload's start until it has landed
    and false otherwise; a recorded launch reads it; every span says which
    thread opened it, and the Chrome trace gives the uploader a row of its
    own, where its spans do not overlap the loop's."""
    with limit(300):
        ch = chan()
        prod = Lockstep(ch, 17, 5)
        src = ShmVolumeSource(ch, GRID, timeout_ms=5000,
                              frame_timeout_ms=5000)
        seen, land = [], src._land
        src._land = lambda view: (seen.append(src.upload_busy),
                                  land(view))[1]
        try:
            assert src.upload_busy is False
            sess, _ = session(src, "obs.enabled=true")
            sess.run(4)
            rec = sess.obs
        finally:
            src.close()
            prod.end()
        assert seen and all(seen) and src.upload_busy is False
        spans = [e for e in rec.events if e["type"] == "span"]
        assert all(isinstance(e.get("thread"), str) for e in spans)
        by_name = lambda name: {e["thread"] for e in spans
                                if e["name"] == name}
        assert by_name("ingest.upload") == {"shm-uploader"}
        assert by_name("dispatch") == by_name("ingest.wait") == \
            by_name("fetch") == {"MainThread"}
        launches = [e["attrs"] for e in spans if e["name"] == "dispatch"]
        assert len(launches) == 4
        assert all(isinstance(a["upload_busy"], bool)
                   and isinstance(a["prev_ready"], bool) for a in launches)
        evs = rec.chrome_trace_events()
        rows = {e["args"]["name"]: e["tid"] for e in evs
                if e.get("ph") == "M" and e["name"] == "thread_name"}
        assert rows["shm-uploader"] != rows["MainThread"]
        assert {e["tid"] for e in evs if e.get("ph") == "X"
                and e["name"] == "ingest.upload"} == {rows["shm-uploader"]}
        assert rows["shm-uploader"] not in {
            e["tid"] for e in evs if e.get("ph") == "X"
            and e["name"] != "ingest.upload"}


def test_close_joins_and_detaches():
    with limit(60):
        ch = chan()
        prod = Lockstep(ch, 17, 3)
        src = ShmVolumeSource(ch, GRID, timeout_ms=5000)
        try:
            src.advance(1)
            thread = src._thread
            assert thread.is_alive() and thread.name == "shm-uploader"
            kept = src.field
            src.close()
            assert not thread.is_alive() and src.consumer.handle is None
            src.close()                                 # again: nothing
            assert np.array_equal(np.asarray(kept), field(17, 0))
            # closed for good: no thread on a closed handle, the last
            # field still shown
            with pytest.raises(RuntimeError, match="closed"):
                src.advance(1)
            assert src._thread is None and src.field is kept
            with pytest.raises(RuntimeError, match="closed"):
                src.consumer.stats()
            assert not any(s["readers"] for s in channel_stats(ch)["slots"])
        finally:
            prod.end()
        never = ShmProducer(chan(), GRID)
        idle = ShmVolumeSource(never.channel, GRID, timeout_ms=1000)
        idle.close()                    # no uploader was ever started
        with pytest.raises(RuntimeError, match="closed"):
            idle.field                  # nor is one started now
        assert idle._thread is None
        never.close()


def test_an_uploader_that_fails_hands_its_error_to_the_loop():
    with limit(60):
        ch = chan()
        prod = Lockstep(ch, 19, 2)
        src = ShmVolumeSource(ch, GRID, timeout_ms=2000)

        def broken(view):
            raise ValueError("the link is down")

        src._land = broken
        try:
            with pytest.raises(RuntimeError, match="uploader ended") as e:
                src.advance(1)
            assert isinstance(e.value.__cause__, ValueError)
            assert not any(s["readers"] for s in channel_stats(ch)["slots"])
        finally:
            src.close()
            prod.end()


PARENT = """
import os, sys, time
sys.path.insert(0, {root!r})
import numpy as np
from chipbench.sources import shm_ring
from scenery_insitu_tpu.ingest import shm
cell = {{"name": {name!r}, "config_file": {{"shape": {{"grid": [8, 8, 8]}},
        "channel_slots": 2, "producer_threads": 2}},
        "traffic_file": {{"field_period_frames": 64,
                          "field_amplitude": 0.05}}}}
proc = shm_ring.start_producer(cell)
assert proc.stdout.read(len(shm_ring.READY)) == shm_ring.READY
proc.stdin.write(np.ones((8, 8, 8), np.float32).tobytes())
proc.stdin.flush()
cons = shm.ShmConsumer(shm_ring.channel_name(cell), (8, 8, 8))
frame, seq = cons.latest(timeout_ms=5000)
assert seq == 1 and float(frame[0, 0, 0]) == 1.0
{unlink}
print(proc.pid, flush=True)
time.sleep(600)
"""


def gone(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] == "Z"
    except OSError:
        return True


@pytest.mark.parametrize("unlinked", [True, False],
                         ids=["name-unlinked", "name-left"])
def test_a_producer_whose_parent_is_killed_goes_with_it(unlinked):
    """The benchmark's producer process (chipbench/sources/shm_ring.py)
    under a parent that is SIGKILLed mid-run: it exits, and the channel the
    harness unlinks once both sides are attached is in `/dev/shm` no more.
    A name that was left (a run killed before the unlink) is superseded by
    the next run's `shm_channel_create`."""
    with limit(120):
        from chipbench.sources import shm_ring

        name = f"ovl_{uuid.uuid4().hex[:10]}"
        # the child shares this process's checkout and TMPDIR: same name
        channel = shm_ring.channel_name({"name": name})
        assert channel.startswith(f"/chipbench_{name}_")
        seg = "/dev/shm" + channel
        parent = subprocess.Popen(
            [sys.executable, "-c", PARENT.format(
                root=ROOT, name=name,
                unlink="shm.unlink(shm_ring.channel_name(cell))"
                if unlinked else "")],
            stdout=subprocess.PIPE, env=dict(os.environ, JAX_PLATFORMS="cpu"))
        try:
            pid = int(parent.stdout.readline())
            assert not gone(pid)
            assert os.path.exists(seg) != unlinked
            parent.kill()
            parent.wait(10)
            deadline = time.monotonic() + 20
            while not gone(pid) and time.monotonic() < deadline:
                time.sleep(0.02)
            assert gone(pid)
            if not unlinked:
                ShmProducer(channel, (4, 4, 4)).close()
            assert not os.path.exists(seg)
        finally:
            parent.kill()
            parent.wait(10)
            unlink(channel)


def test_stall_with_an_uploader_keeps_last_good_and_recovers():
    """The stall path with the field on the device: last-good data under
    one `ingest.stall` row, non-blocking while stalled, and the next frame
    published is rendered by the very next `advance`."""
    with limit(60):
        ch = chan()
        prod = ShmProducer(ch, GRID)
        prod.publish(field(23, 0))
        src = ShmVolumeSource(ch, GRID, timeout_ms=2000,
                              frame_timeout_ms=100)
        obs.clear_ledger()
        try:
            src.advance(1)
            src.advance(1)
            assert src.stalled and src.stall_count == 1
            assert [e["component"] for e in obs.ledger()] == ["ingest.stall"]
            t0 = time.monotonic()
            for _ in range(5):
                src.advance(1)
            assert time.monotonic() - t0 < 0.4 and src.stall_count == 1
            assert np.array_equal(np.asarray(src.field), field(23, 0))
            prod.publish(field(23, 1))
            src.advance(1)
            assert not src.stalled and src.last_seq == 2
            assert np.array_equal(np.asarray(src.field), field(23, 1))
        finally:
            src.close()
            prod.close()
