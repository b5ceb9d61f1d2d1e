"""Multi-host (DCN) distribution — the reference's 8-node MPI deployment
shape (README.md:4-8: one renderer per cluster node, MPI between them;
externals DistributedVolumes.kt:136-139) mapped to JAX's multi-process
runtime:

- ``initialize()`` ≅ MPI_Init: every process connects to the coordinator
  (jax.distributed), after which ``jax.devices()`` is the GLOBAL device
  list and one jitted SPMD program spans all hosts. Collectives ride ICI
  within a host and DCN between hosts — chosen by XLA, not by this code.
- ``global_mesh()`` ≅ COMM_WORLD: the same 1-D compositing mesh the
  single-host pipeline uses, just over global devices, so
  ``distributed_vdi_step`` / ``_mxu`` / hybrid run UNCHANGED.
- ``shard_global()`` builds a global array from each process's local slab
  (the in-situ case: every node's simulation produces its own slab; no
  host ever holds the whole volume).
- ``gather_vdi_compressed()`` is the explicit HOST hop: each process
  compresses its addressable output columns with the variable-length
  segment codec (io.vdi_io.pack_vdi_segments ≅ the reference's
  per-segment LZ4 + MPI_Alltoallv, VDICompositingTest.kt:251-304) and
  process 0 assembles the full frame. Device collectives stay
  uncompressed — compression pays only on DCN/host/disk paths.

Smoke test (single machine, 2 processes — ≅ mpirun -np 2):

    python -m scenery_insitu_tpu.parallel.multihost --launch 2

Each process pins 2 virtual CPU devices, initializes the coordination
service, runs one distributed_vdi_step over the 4-device global mesh
(``MULTIHOST_OK norm=...``), then the flagship temporal MXU chain —
rank-sharded threshold seed + two carried-state frames —
(``MULTIHOST_MXU_OK norm=...``); norms must agree across processes, and
process 0 checks the compressed host gather (``MULTIHOST_GATHER_OK``).
"""

from __future__ import annotations

import os
import sys
from typing import Optional, Tuple

import numpy as np

from scenery_insitu_tpu.parallel.mesh import DEFAULT_AXIS


def initialize(coordinator_address: str, num_processes: int,
               process_id: int, timeout_s: float = 300.0,
               attempt_timeout_s: float = 60.0, fault=None) -> None:
    """≅ MPI_Init. Call before any other JAX use on every process.

    Wrapped in the bounded-backoff ladder of ``utils/retry.Backoff``
    (docs/ROBUSTNESS.md "Liveness supervision"): a coordinator that is
    still starting, a not-yet-scheduled peer or a transient DCN blip no
    longer hangs the fleet silently — each attempt gets
    ``attempt_timeout_s``, every retry lands on the fallback ledger as
    ``multihost.connect``, and the whole ladder gives up (re-raising the
    last error) after ``timeout_s``. ``fault`` (a config.FaultConfig)
    supplies the backoff base/cap; None uses the retry defaults."""
    import time

    import jax

    from scenery_insitu_tpu import obs as _obs
    from scenery_insitu_tpu.utils.retry import Backoff

    bo = (Backoff(fault.backoff_base_s, fault.backoff_cap_s)
          if fault is not None else Backoff())
    deadline = time.monotonic() + timeout_s
    attempt = 0
    while True:
        budget = deadline - time.monotonic()
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
                initialization_timeout=max(
                    1, int(min(attempt_timeout_s, max(budget, 1.0)))))
            return
        except Exception as e:
            try:    # clear any half-initialized client before retrying
                jax.distributed.shutdown()
            except Exception:
                pass
            attempt += 1
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise RuntimeError(
                    f"multihost.initialize: process {process_id} could "
                    f"not reach the coordinator at "
                    f"{coordinator_address} within {timeout_s:.0f}s "
                    f"({attempt} attempts)") from e
            _obs.degrade("multihost.connect", "first-attempt connect",
                         f"retry (attempt {attempt})",
                         f"{type(e).__name__}: {e}", warn=False)
            time.sleep(min(bo.next_delay(), max(0.0, remaining)))


def global_mesh(axis_name: str = DEFAULT_AXIS):
    """1-D mesh over ALL processes' devices (call after initialize())."""
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()), (axis_name,))


def shard_global(local_block: np.ndarray, mesh, axis_name: str = DEFAULT_AXIS
                 ):
    """Build the global z-sharded volume array from THIS process's slab
    (each process contributes its local simulation output; the global
    array is never materialized on one host)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P(axis_name, None, None))
    return jax.make_array_from_process_local_data(sharding, local_block)


def _kv_client():
    """The coordination-service key-value client every jax.distributed
    process holds — the host-side DCN side channel (endpoint exchange,
    barriers, and the blob-allgather fallback below)."""
    import jax

    client = jax._src.distributed.global_state.client
    if client is None:
        raise RuntimeError("jax.distributed is not initialized — the "
                           "coordinator KV store only exists multi-process")
    return client


def kv_put_bytes(key: str, value: bytes) -> None:
    """Publish a small blob under ``key`` in the coordinator KV store
    (base64-string fallback where the bytes API is missing)."""
    client = _kv_client()
    if hasattr(client, "key_value_set_bytes"):
        client.key_value_set_bytes(key, value)
    else:
        import base64

        client.key_value_set(key, base64.b64encode(value).decode())


def kv_get_bytes(key: str, timeout_ms: int = 60_000) -> bytes:
    """Blocking fetch of a `kv_put_bytes` blob (waits for the key)."""
    client = _kv_client()
    if hasattr(client, "blocking_key_value_get_bytes"):
        return client.blocking_key_value_get_bytes(key, timeout_ms)
    import base64

    return base64.b64decode(client.blocking_key_value_get(key, timeout_ms))


def barrier(name: str, timeout_ms: int = 60_000) -> None:
    """Coordination-service barrier across every process (≅ MPI_Barrier
    on the host plane — no device collective, works on any backend)."""
    _kv_client().wait_at_barrier(name, timeout_ms)


_KV_AG_SEQ = [0]          # collective call counter (same order everywhere)


def _device_collectives_ok() -> bool:
    """Can this runtime run cross-process DEVICE collectives? The CPU
    backend cannot ("Multiprocess computations aren't implemented"), so
    multi-process CPU runs — the CI harness, testing/multiproc.py —
    route host gathers through the coordinator KV store instead."""
    import jax

    return jax.process_count() == 1 or jax.default_backend() != "cpu"


def _allgather_blobs(blob: bytes, timeout_ms: int = 120_000):
    """Allgather of one variable-length blob per process: returns
    (blobs [P, 1, maxlen], lengths [P, 1]) — the shared transport of the
    compressed VDI gather and the obs-event merge, and the explicit DCN
    hop of the host path (every byte is counted on the
    ``dcn_bytes_sent`` / ``dcn_bytes_received`` obs counters, the hop
    spans as ``dcn_allgather`` — docs/OBSERVABILITY.md).

    Transport: a padded-uint8 ``process_allgather`` over devices where
    the backend supports cross-process collectives; on a multi-process
    CPU backend it degrades (ledgered ``multihost.transport``) to the
    coordinator KV store — same wire contract, pure host plane."""
    from scenery_insitu_tpu import obs as _obs

    rec = _obs.get_recorder()
    rec.count("dcn_bytes_sent", len(blob))
    if not _device_collectives_ok():
        import jax

        _obs.degrade(
            "multihost.transport", "device-allgather", "coordinator-kv",
            "this backend cannot run cross-process device collectives; "
            "host gathers ride the coordination-service KV store",
            warn=False)
        nproc = jax.process_count()
        pid = jax.process_index()
        seq = _KV_AG_SEQ[0]
        _KV_AG_SEQ[0] += 1
        with rec.span("dcn_allgather", transport="kv", seq=seq):
            kv_put_bytes(f"sitpu/ag/{seq}/{pid}", blob)
            # bounded KV footprint over long runs: retire our own blob
            # from TWO collective generations back — any process at call
            # s has completed call s-1's gets, and it could only start
            # call s-1 after finishing call s-2's gets, so no reader can
            # still need a seq-2 key (best-effort: old jax clients lack
            # key_value_delete; the window stays 2 entries either way)
            if seq >= 2:
                try:
                    _kv_client().key_value_delete(
                        f"sitpu/ag/{seq - 2}/{pid}")
                except Exception:  # sitpu-lint: disable=SITPU-LEDGER — cleanup of an already-consumed key; nothing degrades
                    pass
            parts = []
            for p in range(nproc):
                parts.append(blob if p == pid else kv_get_bytes(
                    f"sitpu/ag/{seq}/{p}", timeout_ms))
        maxlen = max(len(b) for b in parts)
        blobs = np.zeros((nproc, 1, max(maxlen, 1)), np.uint8)
        lengths = np.zeros((nproc, 1), np.int64)
        for p, b in enumerate(parts):
            blobs[p, 0, :len(b)] = np.frombuffer(b, np.uint8)
            lengths[p, 0] = len(b)
            if p != pid:
                rec.count("dcn_bytes_received", len(b))
        return blobs, lengths

    from jax.experimental import multihost_utils

    ln = np.zeros((1,), np.int64)
    ln[0] = len(blob)
    with rec.span("dcn_allgather", transport="device"):
        # normalize to [P, 1] / [P, 1, maxlen]: single-process allgather
        # returns the input without a leading process axis
        lengths = np.asarray(
            multihost_utils.process_allgather(ln)).reshape(-1, 1)
        maxlen = int(lengths.max())
        buf = np.zeros((1, maxlen), np.uint8)
        buf[0, :len(blob)] = np.frombuffer(blob, np.uint8)
        blobs = np.asarray(
            multihost_utils.process_allgather(buf)).reshape(-1, 1, maxlen)
    received = int(lengths.sum() - len(blob))
    if received > 0:
        rec.count("dcn_bytes_received", received)
    return blobs, lengths


def gather_vdi_tiles(vdi, codec: str = "zstd"):
    """Tile-granular host gather (docs/PERF.md "Tile waves"): compress
    each process's addressable column block and, on process 0, YIELD the
    blocks as ``(col0, color, depth)`` in ascending column order, each
    decompressed lazily as the consumer reaches it — rank-0 assembly
    (and anything it feeds, e.g. a VDIPublisher publishing tiles) can
    emit the first columns before the whole frame finishes
    decompressing. Returns a generator on process 0, None elsewhere.

    Wire format: one dense zstd/zlib blob per process (its contiguous
    column block: raw color bytes + depth bytes) with per-process byte
    counts — the variable-length-per-sender idea of the reference's
    compressed gather, one segment per process rather than
    io.vdi_io.pack_vdi_segments' per-destination split (here the exchange
    already happened on-device; only the final gather crosses hosts).
    Transport is jax's process_allgather on a padded uint8 buffer."""
    import jax

    from scenery_insitu_tpu.io.vdi_io import compress, decompress

    if vdi.color.is_fully_addressable:
        # one process holds the whole frame, however it is sharded (on a
        # one-process mesh it leaves slot-major: pipeline._frame_out)
        local_c, local_d = np.asarray(vdi.color), np.asarray(vdi.depth)
    else:
        # addressable column block of this process (contiguous by
        # construction of the 1-D W sharding a multi-process mesh keeps)
        col_shards = sorted(
            (s for s in vdi.color.addressable_shards),
            key=lambda s: s.index[-1].start or 0)
        dep_shards = sorted(
            (s for s in vdi.depth.addressable_shards),
            key=lambda s: s.index[-1].start or 0)
        local_c = np.concatenate([np.asarray(s.data) for s in col_shards],
                                 -1)
        local_d = np.concatenate([np.asarray(s.data) for s in dep_shards],
                                 -1)
    blobs, lengths = _allgather_blobs(
        compress(local_c.tobytes() + local_d.tobytes(), codec))

    if jax.process_index() != 0:
        return None
    nproc = jax.process_count()
    k, ch, h, _ = vdi.color.shape
    ch_d = vdi.depth.shape[1]

    def tiles():
        from scenery_insitu_tpu import obs as _obs

        rec = _obs.get_recorder()
        col0 = 0
        for p in range(nproc):
            with rec.span("dcn_decompress", source_rank=p,
                          bytes=int(lengths[p, 0])):
                raw = decompress(bytes(blobs[p, 0, :int(lengths[p, 0])]),
                                 codec)
            arr = np.frombuffer(raw, np.float32)
            wseg = arr.size // (k * (ch + ch_d) * h)
            nc = k * ch * h * wseg
            yield (col0, arr[:nc].reshape(k, ch, h, wseg),
                   arr[nc:].reshape(k, ch_d, h, wseg))
            col0 += wseg

    return tiles()


def gather_vdi_compressed(vdi, codec: str = "zstd"
                          ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Host hop: compress each process's addressable output columns and
    assemble the full (color, depth) on process 0 (returns None
    elsewhere). The whole-frame view of `gather_vdi_tiles` — same wire
    format and transport, blocks concatenated in column order."""
    tiles = gather_vdi_tiles(vdi, codec)
    if tiles is None:
        return None
    cols, deps = [], []
    for _, c, d in tiles:
        cols.append(c)
        deps.append(d)
    return np.concatenate(cols, -1), np.concatenate(deps, -1)


def gather_obs_events(recorder) -> Optional[list]:
    """Rank-0 merge of the observability layer (obs.Recorder): every
    process contributes its structured events + summary (rank is already
    in every event, so the merge is a concatenation sorted by timestamp);
    returns the merged event list on process 0, None elsewhere. Single-
    process: a plain local snapshot, no collective. The blob rides the
    same padded-allgather transport as ``gather_vdi_compressed`` — zlib
    (stdlib, never degrades) since telemetry JSON is small.

    Each rank's ``ts`` is relative to its OWN recorder epoch, so the
    merge rebases every event onto the earliest epoch (via the
    recorder's wall-clock ``epoch_unix``) before sorting — without this,
    a rank whose session started late would sort seconds early."""
    import json as _json
    import zlib

    import jax

    payload = {"events": recorder.events, "summary": recorder.summary(),
               "epoch_unix": recorder.epoch_unix}
    if jax.process_count() == 1:
        return sorted(payload["events"], key=lambda e: e.get("ts", 0.0)) \
            + [{"type": "summary", **payload["summary"]}]

    blobs, lengths = _allgather_blobs(
        zlib.compress(_json.dumps(payload).encode()))

    if jax.process_index() != 0:
        return None
    payloads = []
    for p in range(jax.process_count()):
        raw = zlib.decompress(bytes(blobs[p, 0, :int(lengths[p, 0])]))
        payloads.append(_json.loads(raw))
    base = min(d["epoch_unix"] for d in payloads)
    events, summaries = [], []
    for d in payloads:
        shift = d["epoch_unix"] - base
        for ev in d["events"]:
            ev = dict(ev)
            ev["ts"] = ev.get("ts", 0.0) + shift
            events.append(ev)
        summaries.append({"type": "summary", **d["summary"]})
    events.sort(key=lambda e: e.get("ts", 0.0))
    return events + summaries


# --------------------------------------------------------------- smoke test

def _worker(coordinator: str, nproc: int, pid: int) -> None:
    initialize(coordinator, nproc, pid)

    import jax
    import jax.numpy as jnp

    from scenery_insitu_tpu.config import CompositeConfig, VDIConfig
    from scenery_insitu_tpu.core.camera import Camera
    from scenery_insitu_tpu.core.transfer import for_dataset
    from scenery_insitu_tpu.parallel.pipeline import distributed_vdi_step
    from scenery_insitu_tpu.sim import grayscott as gs

    mesh = global_mesh()
    n = len(jax.devices())
    print(f"[mh {pid}] processes={jax.process_count()} global_devices={n}",
          flush=True)

    d_local_proc = 8 * (n // jax.process_count())
    grid_h = grid_w = 16
    width, height = 8 * n, 16

    # every process seeds the SAME global state and slices out its slab —
    # deterministic, so the result must match a single-process run
    st = gs.GrayScott.init((8 * n, grid_h, grid_w), n_seeds=4)
    z0 = pid * d_local_proc
    local_u = np.asarray(st.v)[z0:z0 + d_local_proc]
    field = shard_global(local_u, mesh)

    tf = for_dataset("gray_scott")
    cam = Camera.create((0.0, 0.4, 3.0), fov_y_deg=50.0, near=0.5, far=20.0)
    step = distributed_vdi_step(
        mesh, tf, width, height,
        VDIConfig(max_supersegments=4, adaptive_iters=2),
        CompositeConfig(max_output_supersegments=6, adaptive_iters=2),
        max_steps=24)
    origin = jnp.array([-1.0, -1.0, -1.0], jnp.float32)
    spacing = jnp.array([2.0 / 16, 2.0 / 16, 2.0 / (8 * n)], jnp.float32)
    vdi = step(field, origin, spacing, cam)

    # replicated reduction: every process must report the same value
    norm = float(jax.jit(lambda c: jnp.linalg.norm(c))(vdi.color))
    print(f"MULTIHOST_OK pid={pid} norm={norm:.6f}", flush=True)

    # flagship path across processes: MXU slice march with carried
    # temporal threshold state (rank-sharded through the global mesh)
    from scenery_insitu_tpu.config import SliceMarchConfig
    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.parallel.pipeline import (
        distributed_initial_threshold_mxu, distributed_vdi_step_mxu_temporal)

    spec = slicer.make_spec(cam, (8 * n, grid_h, grid_w),
                            SliceMarchConfig(matmul_dtype="f32"),
                            multiple_of=n)
    cfg_t = VDIConfig(max_supersegments=4, adaptive_mode="temporal")
    comp = CompositeConfig(max_output_supersegments=6, adaptive_iters=2)
    thr = distributed_initial_threshold_mxu(mesh, tf, spec, cfg_t)(
        field, origin, spacing, cam)
    step_t = distributed_vdi_step_mxu_temporal(mesh, tf, spec, cfg_t, comp)
    for _ in range(2):
        (vdi_t, _), thr = step_t(field, origin, spacing, cam, thr)
    norm_t = float(jax.jit(lambda c: jnp.linalg.norm(c))(vdi_t.color))
    print(f"MULTIHOST_MXU_OK pid={pid} norm={norm_t:.6f}", flush=True)

    gathered = gather_vdi_compressed(vdi)
    if pid == 0:
        color, depth = gathered
        assert color.shape == (6, 4, height, width), color.shape
        assert np.isfinite(color).all()
        print(f"MULTIHOST_GATHER_OK shape={color.shape} "
              f"norm={np.linalg.norm(color):.6f}", flush=True)
    jax.distributed.shutdown()


def _launch(nproc: int, devices_per_proc: int = 2) -> int:
    """Spawn nproc workers on this machine (≅ mpirun -np N) and verify
    their replicated outputs agree."""
    import socket
    import subprocess

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coordinator = f"127.0.0.1:{port}"

    from scenery_insitu_tpu.utils.backend import virtual_mesh_env

    procs = []
    for pid in range(nproc):
        base = dict(os.environ)
        base["XLA_FLAGS"] = " ".join(
            f for f in base.get("XLA_FLAGS", "").split()
            if "host_platform_device_count" not in f)
        env = virtual_mesh_env(devices_per_proc, base)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "scenery_insitu_tpu.parallel.multihost",
             "--coordinator", coordinator, "--processes", str(nproc),
             "--process-id", str(pid)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            cwd=os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))))

    norms = {}
    mxu_norms = {}
    ok = True
    for pid, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            # a wedged worker must still yield a parseable verdict and
            # must not leave its siblings bound to the coordinator port
            for q in procs:
                if q.poll() is None:
                    q.kill()
            for q in procs:     # reap: SIGKILL delivery is asynchronous
                try:
                    q.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    pass
            print(f"LAUNCH_FAILED worker {pid} timed out")
            return 1
        text = out.decode("utf-8", "replace")
        print(text)
        if p.returncode != 0:
            ok = False
        for line in text.splitlines():
            if line.startswith("MULTIHOST_OK"):
                norms[pid] = float(line.rsplit("norm=", 1)[1])
            elif line.startswith("MULTIHOST_MXU_OK"):
                mxu_norms[pid] = float(line.rsplit("norm=", 1)[1])

    def agree(d):
        return len(d) == nproc and len(set(round(v, 4)
                                           for v in d.values())) == 1

    if ok and agree(norms) and agree(mxu_norms):
        print(f"LAUNCH_OK processes={nproc} norm={norms[0]:.6f} "
              f"mxu_norm={mxu_norms[0]:.6f}")
        return 0
    print("LAUNCH_FAILED", norms, mxu_norms)
    return 1


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--launch", type=int, default=0,
                    help="spawn N single-machine processes (smoke test)")
    ap.add_argument("--coordinator", default=None)
    ap.add_argument("--processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    args = ap.parse_args()

    if args.launch:
        sys.exit(_launch(args.launch))

    _worker(args.coordinator, args.processes, args.process_id)
