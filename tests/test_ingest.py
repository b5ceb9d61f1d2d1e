"""Shared-memory ingest bridge tests (SURVEY.md §7 step 7, layer L1):
protocol round-trips, never-blocking producer, zero-copy pinning, the C++
demo simulation as external producer, and an InSituSession driven by it
(≅ the reference's shm_mpiproducer/consumer pair under mpirun and the
C++-drives-renderer operator boundary)."""

import os
import subprocess
import threading
import time
import uuid

import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    subprocess.run(["which", "g++"], capture_output=True).returncode != 0,
    reason="no C++ toolchain")

from scenery_insitu_tpu.ingest.shm import (DEMO_PRODUCER, ShmConsumer,
                                           ShmProducer, ShmVolumeSource,
                                           ensure_built, unlink)


def _chan():
    return f"/sitpu_test_{uuid.uuid4().hex[:12]}"


def test_build():
    assert os.path.exists(ensure_built())


def test_roundtrip_and_ordering():
    shape = (8, 8, 8)
    ch = _chan()
    prod = ShmProducer(ch, shape)
    cons = ShmConsumer(ch, shape, timeout_ms=2000)
    try:
        seqs = []
        for i in range(5):
            frame = np.full(shape, float(i), np.float32)
            s = prod.publish(frame)
            assert s > 0
            got = cons.latest(timeout_ms=1000)
            assert got is not None
            arr, seq = got
            seqs.append(seq)
            np.testing.assert_array_equal(arr, frame)
        assert seqs == sorted(seqs)
        # no new frame -> poll returns None immediately
        assert cons.latest(timeout_ms=0) is None
    finally:
        cons.close()
        prod.close()


def test_consumer_sees_newest_only():
    """A slow consumer skips intermediate frames (the transport carries
    'the newest state', not a queue — same as the reference's double
    buffer)."""
    shape = (4,)
    ch = _chan()
    prod = ShmProducer(ch, shape)
    cons = ShmConsumer(ch, shape, timeout_ms=2000)
    try:
        for i in range(10):
            prod.publish(np.full(shape, float(i), np.float32))
        arr, seq = cons.latest(timeout_ms=1000)
        assert seq == 10
        np.testing.assert_array_equal(arr, np.full(shape, 9.0, np.float32))
    finally:
        cons.close()
        prod.close()


def test_producer_never_blocks_when_readers_pin_everything():
    shape = (4,)
    ch = _chan()
    prod = ShmProducer(ch, shape, nslots=2)
    cons = ShmConsumer(ch, shape, timeout_ms=2000)
    try:
        assert prod.publish(np.zeros(shape, np.float32)) == 1
        pinned, _ = cons.latest(timeout_ms=1000, copy=False)
        # slot 0 = latest (skipped), its twin is pinned? with nslots=2 the
        # writer must avoid the latest slot AND every pinned slot
        s2 = prod.publish(np.ones(shape, np.float32))
        s3 = prod.publish(np.full(shape, 2.0, np.float32))
        # at least one of the writes must have been dropped (seq == 0) or
        # succeeded without corrupting the pinned view
        np.testing.assert_array_equal(np.asarray(pinned),
                                      np.zeros(shape, np.float32))
        assert (s2 == 0) or (s3 == 0) or True  # no deadlock is the point
        cons.release(pinned.slot)
        assert prod.publish(np.full(shape, 3.0, np.float32)) > 0
    finally:
        cons.close()
        prod.close()


def test_zero_copy_view_aliases_shm():
    shape = (16,)
    ch = _chan()
    prod = ShmProducer(ch, shape, nslots=3)
    cons = ShmConsumer(ch, shape, timeout_ms=2000)
    try:
        prod.publish(np.arange(16, dtype=np.float32))
        pinned, _ = cons.latest(copy=False, timeout_ms=1000)
        assert not pinned.flags.owndata          # aliases the mapping
        np.testing.assert_array_equal(np.asarray(pinned),
                                      np.arange(16, dtype=np.float32))
        cons.release(pinned.slot)
    finally:
        cons.close()
        prod.close()


def test_blocking_wait_wakes_on_publish():
    shape = (4,)
    ch = _chan()
    prod = ShmProducer(ch, shape)
    cons = ShmConsumer(ch, shape, timeout_ms=2000)
    result = {}

    def waiter():
        result["got"] = cons.latest(timeout_ms=5000)

    t = threading.Thread(target=waiter)
    try:
        t.start()
        time.sleep(0.2)                          # let it block
        prod.publish(np.full(shape, 7.0, np.float32))
        t.join(timeout=5)
        assert not t.is_alive()
        arr, seq = result["got"]
        np.testing.assert_array_equal(arr, np.full(shape, 7.0, np.float32))
    finally:
        cons.close()
        prod.close()


def test_shm_source_stall_and_recover():
    """Satellite (ISSUE 11): a stalled/dead producer must not kill the
    render loop — ShmVolumeSource keeps rendering last-good data under
    an `ingest.stall` ledger row, polls without blocking while stalled,
    and recovers the moment frames resume."""
    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.ingest.shm import ShmVolumeSource

    shape = (6, 6, 6)
    ch = _chan()
    prod = ShmProducer(ch, shape)
    prod.publish(np.full(shape, 1.0, np.float32))
    src = ShmVolumeSource(ch, shape, timeout_ms=2000,
                          frame_timeout_ms=100, device_put=False)
    try:
        src.advance(1)
        np.testing.assert_array_equal(np.asarray(src.field),
                                      np.full(shape, 1.0, np.float32))
        assert not src.stalled
        # producer goes quiet: the source stalls, keeps last-good data
        src.advance(1)
        assert src.stalled and src.stall_count == 1
        assert any(e["component"] == "ingest.stall"
                   for e in obs.ledger())
        np.testing.assert_array_equal(np.asarray(src.field),
                                      np.full(shape, 1.0, np.float32))
        # while stalled, advance polls non-blocking (no 100 ms waits)
        t0 = time.monotonic()
        for _ in range(5):
            src.advance(1)
        assert time.monotonic() - t0 < 0.4
        assert src.stall_count == 1          # one episode, minted once
        # frames resume: the stall clears and new data renders
        prod.publish(np.full(shape, 2.0, np.float32))
        src.advance(1)
        assert not src.stalled
        np.testing.assert_array_equal(np.asarray(src.field),
                                      np.full(shape, 2.0, np.float32))
    finally:
        src.close()
        prod.close()


def test_sharded_source_stall_keeps_last_good():
    """The multi-rank twin: a silent producer SET stalls the sharded
    source onto last-good data (ledgered), without blocking the loop."""
    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.ingest.shm import ShmShardedVolumeSource

    jax = pytest.importorskip("jax")
    if len(jax.devices()) < 1:
        pytest.skip("no devices")
    from scenery_insitu_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(1)
    shape = (4, 4, 4)
    ch = _chan()
    prod = ShmProducer(ch, shape)
    prod.publish(np.full(shape, 3.0, np.float32))
    src = ShmShardedVolumeSource([ch], shape, mesh, timeout_ms=2000,
                                 frame_timeout_ms=100)
    try:
        src.advance()
        assert float(np.asarray(src.field)[0, 0, 0]) == 3.0
        src.advance()                        # nothing newer -> stall
        assert src.stalled
        assert any(e["component"] == "ingest.stall"
                   for e in obs.ledger())
        t0 = time.monotonic()
        src.advance()                        # stalled advances don't block
        assert time.monotonic() - t0 < 0.4
        prod.publish(np.full(shape, 4.0, np.float32))
        src.advance()
        assert not src.stalled
        assert float(np.asarray(src.field)[0, 0, 0]) == 4.0
    finally:
        src.close()
        prod.close()


def test_cpp_demo_producer_field_mode():
    """Consume frames produced by the standalone C++ simulation binary —
    the true cross-language operator boundary."""
    ensure_built()
    ch = _chan()
    d = 12
    proc = subprocess.Popen(
        [DEMO_PRODUCER, ch, "field", str(d), "50", "2"],
        stdout=subprocess.DEVNULL)
    try:
        cons = ShmConsumer(ch, (d, d, d), timeout_ms=5000)
        seqs = []
        for _ in range(5):
            got = cons.latest(timeout_ms=2000)
            assert got is not None
            arr, seq = got
            seqs.append(seq)
            assert np.isfinite(arr).all()
            assert arr.max() > 0.5               # the Gaussian blob peak
        assert seqs == sorted(seqs) and len(set(seqs)) == 5
        cons.close()
    finally:
        proc.wait(timeout=10)
        unlink(ch)              # the C++ producer leaves its channel


def test_session_driven_by_external_cpp_sim():
    """InSituSession rendering a volume stream from the C++ producer —
    the reference's headline capability (OpenFPM sim drives renderer),
    standalone-testable (its repo 'can not be used standalone')."""
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.parallel.mesh import make_mesh
    from scenery_insitu_tpu.runtime.session import InSituSession

    ensure_built()
    ch = _chan()
    d = 16
    proc = subprocess.Popen(
        [DEMO_PRODUCER, ch, "field", str(d), "400", "2"],
        stdout=subprocess.DEVNULL)
    try:
        src = ShmVolumeSource(ch, (d, d, d), timeout_ms=5000)
        cfg = FrameworkConfig().with_overrides(
            "render.width=32", "render.height=24", "render.max_steps=16",
            "vdi.max_supersegments=4", "vdi.adaptive_iters=1",
            "composite.max_output_supersegments=4",
            "composite.adaptive_iters=1", "sim.steps_per_frame=1",
            "runtime.dataset=procedural")
        sess = InSituSession(cfg, mesh=make_mesh(2), sim=src)
        payload = sess.run(3)
        assert payload["vdi_color"].shape == (4, 4, 24, 32)
        assert np.isfinite(payload["vdi_color"]).all()
        assert payload["vdi_color"].max() > 0.0  # blob is visible
        sess.close()            # the session's end: uploader, consumer
        assert src.consumer.handle is None
    finally:
        proc.kill()
        proc.wait(timeout=10)
        unlink(ch)


def _run_slab_producers(n: int, d: int, frames: int):
    """Run n slab producers to completion (one per rank) + one whole-field
    producer of the same deterministic Gaussian; returns (slab_channels,
    whole_channel). Exited producers leave their final frame in the ring,
    so consumers see one static, bit-identical frame set — parity between
    the multi-rank and whole-field feeds is then exact, not statistical."""
    ensure_built()
    chans = [_chan() for _ in range(n)]
    whole = _chan()
    procs = [subprocess.Popen(
        [DEMO_PRODUCER, c, "slab", str(d), str(frames), "0", str(r), str(n)],
        stdout=subprocess.DEVNULL) for r, c in enumerate(chans)]
    procs.append(subprocess.Popen(
        [DEMO_PRODUCER, whole, "field", str(d), str(frames), "0"],
        stdout=subprocess.DEVNULL))
    for p in procs:
        assert p.wait(timeout=30) == 0
    return chans, whole


def test_sharded_source_assembles_coherent_global_field():
    """N external slab producers -> ONE mesh-sharded global jax.Array:
    values bit-equal to the whole-field producer's frame, shards placed
    one-per-device with the distributed pipeline's sharding (so the
    session's shard_volume re-placement is a no-op)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from scenery_insitu_tpu.ingest.shm import ShmShardedVolumeSource
    from scenery_insitu_tpu.parallel.mesh import make_mesh

    n, d = 2, 16
    chans, whole = _run_slab_producers(n, d, frames=3)
    mesh = make_mesh(n)
    src = ShmShardedVolumeSource(chans, (d // n, d, d), mesh,
                                 timeout_ms=5000, frame_timeout_ms=300)
    try:
        field = src.field
        assert field.shape == (d, d, d)
        assert len(set(src.last_seqs)) == 1          # coherent frame set
        assert field.sharding.is_equivalent_to(
            NamedSharding(mesh, P(mesh.axis_names[0], None, None)),
            field.ndim)
        shards = {s.device: s.data.shape for s in field.addressable_shards}
        assert len(shards) == n
        assert set(shards.values()) == {(d // n, d, d)}
        ref = ShmConsumer(whole, (d, d, d), timeout_ms=5000)
        want, _ = ref.latest(timeout_ms=2000)
        ref.close()
        assert np.array_equal(np.asarray(field), want)
        # advance with exited producers keeps the last coherent frame
        src.advance(1)
        assert src.last_seqs and np.asarray(src.field).max() > 0.5
    finally:
        src.close()
        from scenery_insitu_tpu.ingest.shm import unlink
        for c in chans + [whole]:
            unlink(c)


def test_session_driven_by_multirank_external_producers():
    """The last operator-boundary gap (round-4 VERDICT item 5): N
    demo_producer processes, one per rank slab, feed the DISTRIBUTED
    pipeline through an InSituSession over the virtual mesh — and the
    render equals the same session fed the whole field through one
    channel (≅ DistributedVolumeRenderer.kt:136-160's per-rank MPI
    partners vs a single-source run)."""
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.ingest.shm import (ShmShardedVolumeSource,
                                               unlink)
    from scenery_insitu_tpu.parallel.mesh import make_mesh
    from scenery_insitu_tpu.runtime.session import InSituSession

    n, d = 4, 16
    chans, whole = _run_slab_producers(n, d, frames=3)
    mesh = make_mesh(n)
    cfg = FrameworkConfig().with_overrides(
        "render.width=32", "render.height=24", "render.max_steps=16",
        "vdi.max_supersegments=4", "vdi.adaptive_iters=1",
        "composite.max_output_supersegments=4",
        "composite.adaptive_iters=1", "sim.steps_per_frame=1",
        "runtime.dataset=procedural")
    src_multi = ShmShardedVolumeSource(chans, (d // n, d, d), mesh,
                                       timeout_ms=5000,
                                       frame_timeout_ms=300)
    # channels already exist (producers ran to completion), so the short
    # timeout only bounds the keep-last-frame wait per advance
    src_single = ShmVolumeSource(whole, (d, d, d), timeout_ms=1500)
    try:
        pay_m = InSituSession(cfg, mesh=mesh, sim=src_multi).run(2)
        pay_s = InSituSession(cfg, mesh=mesh, sim=src_single).run(2)
        assert pay_m["vdi_color"].max() > 0.0        # blob visible
        np.testing.assert_array_equal(pay_m["vdi_color"],
                                      pay_s["vdi_color"])
        np.testing.assert_array_equal(pay_m["vdi_depth"],
                                      pay_s["vdi_depth"])
    finally:
        src_multi.close()
        src_single.close()
        for c in chans + [whole]:
            unlink(c)


def test_concurrent_stress_no_torn_frames():
    """Race stress (the reference ships NO race detection — SURVEY §5):
    one producer process-thread publishing checksummed frames as fast as
    possible, two consumer threads reading concurrently with and without
    copy. Every observed frame must be internally consistent (checksum
    matches its sequence stamp) and sequences must be non-decreasing per
    consumer — i.e. no torn reads, no reordering, under real contention."""
    chan = _chan()
    shape = (64, 257)      # odd second dim: exercises unaligned strides
    frames = 400
    prod = ShmProducer(chan, shape, nslots=4)
    stop = threading.Event()
    errors = []

    def producer():
        base = np.empty(shape, np.float32)
        for i in range(1, frames + 1):
            base.fill(float(i))
            base[-1, -1] = i * 2.0    # tail stamp: torn-write detector
            prod.publish(base)
        stop.set()

    def consumer(copy: bool):
        con = ShmConsumer(chan, shape, timeout_ms=2000)
        last = 0.0
        deadline = time.time() + 60     # bound the never-saw-a-frame case
        try:
            while ((not stop.is_set() or last == 0.0)
                   and time.time() < deadline):
                got = con.latest(timeout_ms=200, copy=copy)
                if got is None:
                    continue
                frame, _seq = got
                head = float(frame[0, 0])
                tail = float(frame[-1, -1])
                mid = float(frame[shape[0] // 2, shape[1] // 2])
                if not copy:
                    con.release(frame.slot)
                if head < last:
                    errors.append(f"value went backwards {last} -> {head}")
                if tail != head * 2.0 or mid != head:
                    errors.append(
                        f"torn frame {head}: tail {tail} mid {mid}")
                last = head
        except Exception as e:      # surfaced by the main thread
            errors.append(repr(e))
        finally:
            con.close()

    ths = [threading.Thread(target=consumer, args=(True,)),
           threading.Thread(target=consumer, args=(False,))]
    for t in ths:
        t.start()
    try:
        producer()
    finally:
        stop.set()      # a producer error must not leave consumers spinning
    for t in ths:
        t.join(timeout=30)
        assert not t.is_alive(), "consumer thread wedged"
    prod.close()
    assert not errors, errors[:5]
