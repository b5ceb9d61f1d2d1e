"""Python bindings for the C++ shared-memory frame transport
(SURVEY.md §7 step 7 — layer L1, the sim↔renderer operator boundary).

The reference crossed this boundary with SysV shm + JNI
``NewDirectByteBuffer`` zero-copy handoff (SharedSpheresExample.cpp:54);
here ctypes maps the C ABI of ``native/shm_transport.cpp`` and the consumer
exposes each pinned slot as a zero-copy numpy view, which ``device_put``
then ships host→HBM (the one copy a TPU cannot avoid — SURVEY.md §7 "hard
parts"; overlap it with compute by dispatching before blocking).

``ShmVolumeSource`` adapts a channel to the session loop's sim-facade
protocol (``advance(n)`` + ``.field``), so an external C++/OpenFPM-style
simulation can drive InSituSession exactly like the built-in sims — the
``addVolume/updateVolume`` operator boundary of the reference
(DistributedVolumes.kt:147-250) collapses to "publish a frame".
"""

from __future__ import annotations

import contextlib
import ctypes
import os
import subprocess
import threading
import time
from typing import Optional, Sequence, Tuple

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "native")
# SITPU_NATIVE_BUILD selects the Makefile build variant: "build" (the
# default) or "build-asan" (`make asan` — the
# -fsanitize=address,undefined instrumented .so the CI sanitizer job
# runs the ingest tests against; needs LD_PRELOAD of the ASan runtime,
# see native/Makefile)
_BUILD_DIR = os.environ.get("SITPU_NATIVE_BUILD", "build")
_MAKE_TARGET = "asan" if _BUILD_DIR == "build-asan" else "all"
_LIB_PATH = os.path.join(_NATIVE_DIR, _BUILD_DIR, "libshm_transport.so")
DEMO_PRODUCER = os.path.join(_NATIVE_DIR, _BUILD_DIR, "demo_producer")

_lib = None


def _sources_mtime() -> float:
    newest = 0.0
    for name in os.listdir(_NATIVE_DIR):
        if name.endswith((".cpp", ".h", ".hpp")) or name == "Makefile":
            newest = max(newest, os.path.getmtime(
                os.path.join(_NATIVE_DIR, name)))
    return newest


def ensure_built(force: bool = False) -> str:
    """Build the native library on first use, and REBUILD when any source
    is newer than the binary — a stale .so from an older checkout otherwise
    fails at ctypes symbol lookup with an opaque 'undefined symbol'."""
    stale = (not os.path.exists(_LIB_PATH)
             or os.path.getmtime(_LIB_PATH) < _sources_mtime())
    if force or stale:
        subprocess.run(["make", "-C", _NATIVE_DIR, _MAKE_TARGET],
                       check=True, capture_output=True)
    return _LIB_PATH


def _load():
    global _lib
    if _lib is not None:
        return _lib
    lib = ctypes.CDLL(ensure_built())
    lib.shm_channel_create.restype = ctypes.c_void_p
    lib.shm_channel_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                       ctypes.c_uint32]
    lib.shm_producer_acquire.restype = ctypes.c_void_p
    lib.shm_producer_acquire.argtypes = [ctypes.c_void_p]
    lib.shm_producer_publish.restype = ctypes.c_uint64
    lib.shm_producer_publish.argtypes = [ctypes.c_void_p]
    lib.shm_channel_frames_dropped.restype = ctypes.c_uint64
    lib.shm_channel_frames_dropped.argtypes = [ctypes.c_void_p]
    lib.shm_consumer_open.restype = ctypes.c_void_p
    lib.shm_consumer_open.argtypes = [ctypes.c_char_p]
    lib.shm_channel_slot_size.restype = ctypes.c_uint64
    lib.shm_channel_slot_size.argtypes = [ctypes.c_void_p]
    lib.shm_channel_nslots.restype = ctypes.c_uint32
    lib.shm_channel_nslots.argtypes = [ctypes.c_void_p]
    lib.shm_consumer_latest.restype = ctypes.c_int32
    lib.shm_consumer_latest.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                        ctypes.POINTER(ctypes.c_void_p),
                                        ctypes.POINTER(ctypes.c_uint64)]
    lib.shm_consumer_release.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.shm_channel_close.argtypes = [ctypes.c_void_p]
    lib.shm_channel_unlink.restype = ctypes.c_int
    lib.shm_channel_unlink.argtypes = [ctypes.c_char_p]
    lib.shm_channel_stats.restype = ctypes.c_uint32
    lib.shm_channel_stats.argtypes = [ctypes.c_void_p,
                                      ctypes.POINTER(ctypes.c_uint64),
                                      ctypes.c_uint32]
    lib.shm_channel_reset_readers.restype = ctypes.c_uint32
    lib.shm_channel_reset_readers.argtypes = [ctypes.c_void_p]
    _lib = lib
    return lib


def _stats_of(lib, h, channel: str) -> dict:
    """The control block behind an open handle, as `channel_stats` gives
    it."""
    # size the buffer from the channel's actual slot count instead of a
    # fixed 32 (which silently relied on kMaxSlots=8 in the C++ side)
    nslots_c = int(lib.shm_channel_nslots(h))
    need = 8 + 2 * nslots_c
    buf = (ctypes.c_uint64 * need)()
    n = lib.shm_channel_stats(h, buf, need)
    if n == 0:
        raise OSError(
            f"shm_channel_stats returned no data for {channel!r} "
            f"(buffer {need} u64, nslots {nslots_c})")
    vals = list(buf[:n])
    nslots = int(vals[0])
    return {
        "channel": channel,
        "nslots": nslots,
        "slot_bytes": int(vals[1]),
        "last_seq": int(vals[2]),
        "latest_slot": int(vals[3]) - 1,
        "waiters": int(vals[4]),
        "writer_attached": bool(vals[5]),
        "frames_dropped": int(vals[6]),
        "slots": [{"readers": int(vals[7 + 2 * i]),
                   "seq": int(vals[8 + 2 * i])}
                  for i in range(nslots)],
        "consumed_seq": int(vals[7 + 2 * nslots]),
    }


def channel_stats(channel: str) -> dict:
    """Inspect a live channel's control block (≅ sem_get.cpp's semaphore
    dump, reference src/test/cpp/sem_get.cpp). Raises FileNotFoundError if
    the channel does not exist. ``consumed_seq`` is the newest sequence
    number any reader has pinned: what a producer in lockstep with its
    reader waits for (``ShmProducer.stats`` reads the same block through
    the producer's own handle, also once the name is unlinked)."""
    lib = _load()
    h = lib.shm_consumer_open(channel.encode())
    if not h:
        raise FileNotFoundError(f"no shm channel {channel!r}")
    try:
        return _stats_of(lib, h, channel)
    finally:
        lib.shm_channel_close(h)


def reset_readers(channel: str) -> int:
    """Clear stale reader pins left by crashed consumers (≅ sem_reset.cpp's
    stuck-semaphore recovery). Returns the number of pins cleared."""
    lib = _load()
    h = lib.shm_consumer_open(channel.encode())
    if not h:
        raise FileNotFoundError(f"no shm channel {channel!r}")
    try:
        return int(lib.shm_channel_reset_readers(h))
    finally:
        lib.shm_channel_close(h)


def unlink(channel: str) -> bool:
    """Remove a channel from the namespace (live handles keep their maps)."""
    return _load().shm_channel_unlink(channel.encode()) == 0


class ShmProducer:
    """Publish fixed-shape f32 frames (the simulation side; ≅ ShmAllocator's
    shm_alloc/shm_free cycle, ShmAllocator.cpp:59-151)."""

    def __init__(self, channel: str, shape: Sequence[int], nslots: int = 3):
        self.lib = _load()
        self.shape = tuple(shape)
        self.nbytes = int(np.prod(self.shape)) * 4
        self.channel = channel
        self.handle = self.lib.shm_channel_create(
            channel.encode(), self.nbytes, nslots)
        if not self.handle:
            raise OSError(f"could not create shm channel {channel!r}")

    def acquire(self) -> Optional[np.ndarray]:
        """The slot the next frame goes into, as a writable array of the
        channel's shape, to be filled in place and then ``commit``ted;
        None (and one more ``frames_dropped``) when every writable slot
        is pinned by a reader — the producer never blocks."""
        ptr = self.lib.shm_producer_acquire(self.handle)
        if not ptr:
            return None
        buf = (ctypes.c_float * (self.nbytes // 4)).from_address(ptr)
        return np.frombuffer(buf, np.float32).reshape(self.shape)

    def commit(self) -> int:
        """Publish the slot last acquired; returns its sequence number."""
        return self.lib.shm_producer_publish(self.handle)

    def publish(self, frame: np.ndarray) -> int:
        """Copy one frame in and publish; returns seq (0 = dropped: every
        writable slot was pinned by slow readers — the producer never
        blocks, matching the reference's guarantee)."""
        frame = np.ascontiguousarray(frame, np.float32)
        if frame.shape != self.shape:
            raise ValueError(f"frame shape {frame.shape} != {self.shape}")
        slot = self.acquire()
        if slot is None:
            return 0
        np.copyto(slot, frame)
        return self.commit()

    def stats(self) -> dict:
        """The channel's control block (`channel_stats`) through this
        handle: it needs no name, so it still answers once the channel
        is unlinked."""
        return _stats_of(self.lib, self.handle, self.channel)

    @property
    def frames_dropped(self) -> int:
        return self.lib.shm_channel_frames_dropped(self.handle)

    def close(self, unlink: bool = True) -> None:
        if self.handle:
            self.lib.shm_channel_close(self.handle)
            self.handle = None
            if unlink:
                self.lib.shm_channel_unlink(self.channel.encode())


class ShmConsumer:
    """Receive frames (the renderer side; ≅ ShmBuffer's
    update_key/attach/detach cycle, ShmBuffer.cpp:29-112)."""

    def __init__(self, channel: str, shape: Sequence[int],
                 timeout_ms: int = 5000, poll_interval_ms: int = 20):
        self.lib = _load()
        self.channel = channel
        self.shape = tuple(shape)
        deadline = time.monotonic() + timeout_ms / 1000.0
        self.handle = None
        while time.monotonic() < deadline:         # producer may start later
            h = self.lib.shm_consumer_open(channel.encode())
            if h:
                self.handle = h
                break
            time.sleep(poll_interval_ms / 1000.0)
        if not self.handle:
            raise TimeoutError(f"shm channel {channel!r} never appeared")
        slot = self.lib.shm_channel_slot_size(self.handle)
        want = int(np.prod(self.shape)) * 4
        if slot != want:
            self.lib.shm_channel_close(self.handle)
            raise ValueError(f"channel slot size {slot} != expected {want}")

    def latest(self, timeout_ms: int = -1, copy: bool = True
               ) -> Optional[Tuple[np.ndarray, int]]:
        """Newest frame strictly newer than the last seen, or None on
        timeout. copy=False returns the zero-copy view WITHOUT releasing
        the slot — call release(slot) (attr ``.slot`` on the array) when
        done, exactly the reference's detach discipline."""
        data = ctypes.c_void_p()
        seq = ctypes.c_uint64()
        idx = self.lib.shm_consumer_latest(self.handle, timeout_ms,
                                           ctypes.byref(data),
                                           ctypes.byref(seq))
        if idx < 0:
            return None
        n = int(np.prod(self.shape))
        buf = (ctypes.c_float * n).from_address(data.value)
        view = np.frombuffer(buf, np.float32).reshape(self.shape)
        if copy:
            out = view.copy()
            self.lib.shm_consumer_release(self.handle, idx)
            return out, seq.value

        class _Pinned(np.ndarray):      # ndarray subclass carrying the slot
            pass

        pinned = view.view(_Pinned)
        pinned.flags.writeable = False
        pinned.slot = idx
        return pinned, seq.value

    def release(self, slot: int) -> None:
        self.lib.shm_consumer_release(self.handle, slot)

    def stats(self) -> dict:
        """The channel's control block (`channel_stats`) through this
        handle, also once the name is unlinked. A read of the block's
        atomics that touches nothing of the handle: safe beside a thread
        that pins and releases slots through the same handle."""
        if not self.handle:
            raise RuntimeError(f"shm consumer of {self.channel!r} is closed")
        return _stats_of(self.lib, self.handle, self.channel)

    def close(self) -> None:
        if self.handle:
            self.lib.shm_channel_close(self.handle)
            self.handle = None


class ShmShardedVolumeSource:
    """Multi-rank external feed for the DISTRIBUTED pipeline: one shm
    channel per compute rank (z-slab order), assembled into one
    mesh-sharded global ``jax.Array`` — each slab is ``device_put`` onto
    its own mesh device and stitched with
    ``make_array_from_single_device_arrays``, so no global host-side
    copy ever exists and the session's ``shard_volume`` re-placement is
    a no-op (the array is already committed with the pipeline's
    sharding). This is the operator boundary the reference crossed with
    per-rank MPI partners each updating their renderer's slab
    (DistributedVolumeRenderer.kt:136-160); here N external producer
    processes feed an InSituSession over a ``Mesh`` exactly like the
    built-in sharded sims.

    ``coherent=True`` (default) additionally requires the per-rank
    sequence numbers of one assembled frame to MATCH — the renderer
    never mixes simulation timesteps across slabs (the reference renders
    whatever each rank last delivered; pass ``coherent=False`` for that
    semantics). Coherence matching assumes lockstep producers (each
    publish succeeds: the ring overwrites, it never drops without
    pinned readers). Before the FIRST frame set is assembled a timeout
    raises, naming the per-rank seqs so a desync is diagnosable; after
    that ``advance`` paces to the producers — it blocks up to
    ``frame_timeout_ms`` for a strictly newer set, then keeps rendering
    the last one (the single-channel source's semantics).

    ``timeout_ms`` bounds channel appearance + the first frame set;
    ``frame_timeout_ms`` (default: ``timeout_ms``) bounds each
    subsequent wait for a newer set.
    """

    def __init__(self, channels: Sequence[str], slab_shape: Sequence[int],
                 mesh, axis_name: Optional[str] = None,
                 timeout_ms: int = 10000, coherent: bool = True,
                 poll_interval_ms: int = 5,
                 frame_timeout_ms: Optional[int] = None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.kind = "external"
        axis = axis_name or mesh.axis_names[0]
        n = mesh.shape[axis]
        if len(channels) != n:
            raise ValueError(f"{len(channels)} channels for a mesh of "
                             f"{n} devices along {axis!r} — need one "
                             "channel per rank, z order")
        self.channels = list(channels)
        self.slab_shape = tuple(slab_shape)
        dn = self.slab_shape[0]
        self.global_shape = (dn * n,) + self.slab_shape[1:]
        self.timeout_ms = timeout_ms
        self.frame_timeout_ms = (timeout_ms if frame_timeout_ms is None
                                 else frame_timeout_ms)
        self.coherent = coherent
        self.poll_interval_ms = poll_interval_ms
        self._jax = jax
        self.sharding = NamedSharding(mesh, P(axis, None, None))
        # mesh device of each rank's shard, in z order (shard r owns
        # global rows [r*dn, (r+1)*dn))
        dmap = self.sharding.addressable_devices_indices_map(
            self.global_shape)
        by_rank = {}
        for dev, idx in dmap.items():
            by_rank[(idx[0].start or 0) // dn] = dev
        self._devices = [by_rank[r] for r in range(n)]
        self.consumers = [ShmConsumer(c, self.slab_shape,
                                      timeout_ms=timeout_ms)
                          for c in channels]
        self._held = [None] * n        # newest (frame, seq) seen per rank
        self._field = None
        self.last_seqs: Tuple[int, ...] = ()
        self.stalled = False

    def _refresh(self, wait_ms: int) -> None:
        for r, con in enumerate(self.consumers):
            got = con.latest(timeout_ms=wait_ms)
            if got is not None:
                self._held[r] = got

    def _aligned(self) -> bool:
        if any(h is None for h in self._held):
            return False
        if not self.coherent:
            return True
        seqs = {h[1] for h in self._held}
        return len(seqs) == 1

    def advance(self, n: int = 1) -> None:   # n meaningless for external
        from scenery_insitu_tpu import obs as _obs

        # while stalled, one non-blocking refresh pass per advance (same
        # policy as ShmVolumeSource: a dead producer set must not
        # throttle the render loop to one frame per timeout)
        wait_ms = (self.timeout_ms if self._field is None
                   else 0 if self.stalled else self.frame_timeout_ms)
        deadline = time.monotonic() + wait_ms / 1000.0
        first = True
        while True:
            # first pass is free (producers may have already published);
            # later passes wait a poll interval inside the consumer
            self._refresh(0 if first else self.poll_interval_ms)
            first = False
            # only a STRICTLY NEWER aligned set completes the wait —
            # otherwise a fast render loop would busy-spin re-rendering
            # the same frame instead of pacing to the producers
            if self._aligned():
                seqs = tuple(h[1] for h in self._held)
                if seqs != self.last_seqs:
                    arrs = [self._jax.device_put(h[0], d)
                            for h, d in zip(self._held, self._devices)]
                    self._field = \
                        self._jax.make_array_from_single_device_arrays(
                            self.global_shape, self.sharding, arrs)
                    self.last_seqs = seqs
                    if self.stalled:
                        self.stalled = False
                        _obs.get_recorder().count(
                            "ingest_stall_recoveries")
                        _obs.get_recorder().event(
                            "ingest_recovered",
                            seqs=[int(s) for s in seqs])
                    return
            if time.monotonic() > deadline:
                if self._field is not None:
                    if not self.stalled:
                        self.stalled = True
                        _obs.get_recorder().count("ingest_stalls")
                        _obs.degrade(
                            "ingest.stall", "live producer frames",
                            "re-rendering last-good frame",
                            "no strictly-newer coherent shm frame set "
                            f"within frame_timeout_ms="
                            f"{self.frame_timeout_ms}; a producer "
                            "stalled or died", warn=False)
                    return                     # keep rendering last frame
                held = [None if h is None else h[1] for h in self._held]
                raise TimeoutError(
                    f"no {'coherent ' if self.coherent else ''}frame set "
                    f"from {self.channels} within {wait_ms} ms "
                    f"(per-rank seqs: {held})")

    @property
    def field(self):
        if self._field is None:
            self.advance(1)
        return self._field

    def stats(self) -> list:
        """Per-rank channel control blocks (seq/drop/reader state)."""
        return [channel_stats(c) for c in self.channels]

    def close(self) -> None:
        for con in self.consumers:
            con.close()


_NO_SPAN = contextlib.nullcontext()     # a span site with obs off


class ShmVolumeSource:
    """Session sim-adapter over a shm channel: plugs an EXTERNAL
    simulation into InSituSession (``advance(n)`` + ``.field``).

    The host -> device hop runs beside the frame loop, not on it (the
    reference's double buffer, SURVEY §0: one field is handed over while
    the last one is rendered). An uploader thread owned by the source
    pins the newest slot WITHOUT copying it (``latest(copy=False)``),
    puts it on the device, waits until the transfer has landed, releases
    the slot, and keeps the landed field until ``advance`` takes it;
    only then does it pin the next one. ``advance`` therefore only swaps
    references (it blocks, in an ``ingest.wait`` span, while no field
    has landed yet), the loop's thread never copies a field, and beside
    the field being marched at most two more are alive on the device:
    the one landed or just taken, and the one on its way. A producer in
    lockstep (one that waits for ``consumed_seq``, ``ShmProducer.stats``)
    gets every field rendered exactly once, in order. Under a
    free-running one each frame renders the newest field at the time
    the one before was TAKEN: one frame interval staler than the
    blocking pull this replaced (newest at `advance`), the price of
    taking the upload off the loop's thread. A landed field is never
    replaced by a newer one: a lockstep producer counts a pin as a
    field rendered (docs/ROBUSTNESS.md).

    Stall supervision (docs/ROBUSTNESS.md): when no strictly-newer frame
    lands within ``frame_timeout_ms`` (default: ``timeout_ms``) the
    source marks itself STALLED — minted once per episode on the
    ``ingest.stall`` ledger — and keeps rendering the last-good frame
    (counter ``ingest_fields_repeated``); while stalled, ``advance``
    polls without blocking so a dead producer cannot throttle the render
    loop to one frame per timeout, unless the channel already holds a
    newer frame that is on its way. The moment frames resume the stall
    clears (``ingest_stall_recoveries`` counter + ``ingest_recovered``
    event).

    ``device_put=False`` lands a host copy of the slot instead (tests).
    ``close()`` joins the uploader and detaches from the channel;
    ``InSituSession.close`` calls it."""

    _POLL_MS = 50       # the uploader's wait for a frame: bounds close()

    def __init__(self, channel: str, grid: Sequence[int],
                 timeout_ms: int = 10000, device_put: bool = True,
                 frame_timeout_ms: Optional[int] = None):
        import jax

        self.kind = "external"
        self.consumer = ShmConsumer(channel, grid, timeout_ms=timeout_ms)
        self.timeout_ms = timeout_ms
        self.frame_timeout_ms = (timeout_ms if frame_timeout_ms is None
                                 else frame_timeout_ms)
        self._device_put = device_put
        self._jax = jax
        # the CPU backend takes an aligned host buffer as the array's own
        # memory instead of copying it: the slot would be read after its
        # release
        self._put_aliases = jax.default_backend() == "cpu"
        self._field = None
        self.stalled = False
        self.stall_count = 0
        self.last_seq = None
        self._cond = threading.Condition()
        self._landed = None         # (field, seq) waiting for `advance`
        # an upload is between its `device_put` and landed: set and
        # cleared by the uploader, read (never waited for) by a recorded
        # session when it launches a frame
        self.upload_busy = False
        self._error = None          # what ended the uploader
        self._closing = False
        self._thread = None         # started by the first advance / field

    # ---------------------------------------------------------- uploader

    def _land(self, view: np.ndarray):
        if self._put_aliases or not self._device_put:
            view = view.copy()
        if not self._device_put:
            return view
        field = self._jax.device_put(view)
        field.block_until_ready()
        return field

    def _upload_one(self, view: np.ndarray, seq: int):
        from scenery_insitu_tpu import obs as _obs

        rec = _obs.get_recorder()
        self.upload_busy = True
        try:
            with (rec.span("ingest.upload", bytes=view.nbytes, seq=seq)
                  if rec.enabled else _NO_SPAN):
                field = self._land(view)
                # inside the span: a reader of the window's events finds
                # the bytes where it finds the upload
                rec.count("ingest_bytes", view.nbytes)
                rec.count("ingest_fields_uploaded")
        finally:
            self.upload_busy = False
        return field

    def _upload_loop(self) -> None:
        try:
            self._upload_until_closed()
        except BaseException as e:      # handed to the loop's thread
            with self._cond:
                self._error = e
                self._cond.notify_all()

    def _upload_until_closed(self) -> None:
        while True:
            with self._cond:
                while self._landed is not None and not self._closing:
                    self._cond.wait()
                if self._closing:
                    return
            got = self.consumer.latest(timeout_ms=self._POLL_MS, copy=False)
            if got is None:
                continue
            view, seq = got[0], int(got[1])
            try:
                field = self._upload_one(view, seq)
            finally:
                self.consumer.release(view.slot)
            with self._cond:
                self._landed = (field, seq)
                self._cond.notify_all()

    def _wait_landed(self, wait_ms: float, take: bool):
        """The landed (field, seq), waiting up to ``wait_ms`` for one;
        None on timeout. ``take`` hands it over for good and lets the
        uploader pin the next slot."""
        if self._closing:
            raise RuntimeError("the shm source is closed")
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._upload_loop, name="shm-uploader", daemon=True)
            self._thread.start()
        deadline = time.monotonic() + wait_ms / 1000.0
        with self._cond:
            while self._landed is None:
                if self._error is not None:
                    raise RuntimeError("the shm uploader ended") \
                        from self._error
                left = deadline - time.monotonic()
                if left <= 0:
                    return None
                self._cond.wait(left)
            got = self._landed
            if take:
                self._landed = None
                self._cond.notify_all()
        return got

    # ------------------------------------------------------ the sim facade

    def advance(self, n: int) -> None:   # n is meaningless for external sims
        from scenery_insitu_tpu import obs as _obs

        rec = _obs.get_recorder()
        # while stalled, poll non-blocking: the loop keeps pacing on
        # last-good data instead of stalling frame_timeout_ms per frame —
        # unless the channel already holds a frame newer than the one
        # rendered last: then frames have resumed and it is on its way
        # (`stats` only reads the control block: the uploader may be
        # inside `latest` on the same handle meanwhile)
        wait = (self.timeout_ms if self._field is None
                else self.frame_timeout_ms if not self.stalled
                or self.consumer.stats()["last_seq"] > self.last_seq else 0)
        with rec.span("ingest.wait") if rec.enabled else _NO_SPAN:
            got = self._wait_landed(wait, take=True)
        if got is None:
            if self._field is None:
                raise TimeoutError("no frame from external simulation")
            rec.count("ingest_fields_repeated")
            if not self.stalled:
                self.stalled = True
                self.stall_count += 1
                rec.count("ingest_stalls")
                _obs.degrade(
                    "ingest.stall", "live producer frames",
                    "re-rendering last-good frame",
                    f"no strictly-newer shm frame within "
                    f"frame_timeout_ms={self.frame_timeout_ms}; "
                    "producer stalled or dead", warn=False)
            return                        # keep rendering the last frame
        self._field, seq = got
        if self.stalled:
            self.stalled = False
            rec.count("ingest_stall_recoveries")
            rec.event("ingest_recovered", seq=seq)
        self.last_seq = seq

    @property
    def field(self):
        """The field the last ``advance`` took. Before the first one: the
        first field to land, which that ``advance`` then takes (a look at
        the shape consumes nothing)."""
        if self._field is not None:
            return self._field
        got = self._wait_landed(self.timeout_ms, take=False)
        if got is None:
            raise TimeoutError("no frame from external simulation")
        return got[0]

    def close(self) -> None:
        """Join the uploader and detach from the channel; the fields
        already on the device stay valid (`field` still shows the last
        one taken). `advance` on a closed source raises."""
        with self._cond:
            self._closing = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        self.consumer.close()
