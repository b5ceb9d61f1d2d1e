"""The in-situ session loop — per-frame orchestration
(≅ ``manageVDIGeneration``, reference DistributedVolumes.kt:683-933, and the
older DistributedVolumeRenderer.kt:450-654).

Where the reference interlocks generation and compositing with
postRenderLambdas, @Volatile flags and AtomicIntegers across three threads
(DistributedVolumes.kt:126-130, 736-796), here one jitted SPMD step runs
sim-advance → VDI generate → all_to_all → composite, and the Python loop
only paces frames, fetches results asynchronously (dispatch frame N+1
before blocking on frame N — JAX's async dispatch gives the overlap the
reference hand-built), feeds sinks, and keeps the per-phase timer taxonomy
(§5 tracing) for the benchmark metrics.

Runs standalone with the built-in simulations — fixing the reference's
"cannot be used standalone" limitation (README.md:16) — or driven
externally by supplying a custom sim adapter (anything with
``advance(n)`` + ``.field``, see VolumeSimAdapter).
"""

from __future__ import annotations

import contextlib
import os
import sys
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from scenery_insitu_tpu import obs as _obs
from scenery_insitu_tpu.config import FrameworkConfig
from scenery_insitu_tpu.core.camera import Camera, orbit
from scenery_insitu_tpu.core.transfer import TransferFunction, for_dataset
from scenery_insitu_tpu.core.vdi import VDI
from scenery_insitu_tpu.core.volume import DATASET_DIMS_XYZ
from scenery_insitu_tpu.obs.hostmem import PAGE, host_pages
from scenery_insitu_tpu.obs.profiler import scoped_step
from scenery_insitu_tpu.parallel.topology import (make_topology_mesh,
                                                  resolve_mesh_topology)
from scenery_insitu_tpu.parallel.pipeline import shard_volume
from scenery_insitu_tpu.runtime import hostheap
from scenery_insitu_tpu.runtime.failsafe import SinkGuard
from scenery_insitu_tpu.runtime.steps import StepEntry, StepTable
from scenery_insitu_tpu.sim import grayscott as gs
from scenery_insitu_tpu.sim import vortex as vx

Sink = Callable[[int, dict], None]


def steer_session(sess, msg: dict) -> None:
    """Apply ONE steering-protocol message to ``sess`` (camera updates
    in place, other kinds to the on_steer callbacks). The zmq drain and
    the in-process path (scenario steering hooks —
    scenery_insitu_tpu/scenarios) route through this same consumer.

    A camera message stays on the host: the session keeps the message's
    own eye and target beside the camera it now holds (``_host_pose``),
    and `camera_regime` decides from them.

    on_steer callbacks run behind the session's SinkGuard: an exception
    in one callback must not kill the drain (or the run) — a callback
    failing ``fault.max_sink_failures`` consecutive times is quarantined
    on the ``session.sink`` ledger."""
    if msg.get("type") == "camera":
        from scenery_insitu_tpu.runtime.streaming import steer_camera
        sess._host_pose = steer_camera(sess.camera, msg, host_pose(sess),
                                       frame=sess.frame_index)
        sess.camera = sess._host_pose.camera
    else:
        sess._sink_guard.run(sess.on_steer, msg, kind="on_steer callback")


def host_pose(sess):
    """The host values of the eye and target of the camera ``sess`` holds
    (core/camera.HostPose), or None where it has none for THAT object: a
    camera whose leaves were computed on the device (the benchmark orbit,
    a prewarm's synthetic camera), restored from a checkpoint or assigned
    by a caller."""
    pose = sess._host_pose
    return pose if pose is not None and pose.camera is sess.camera else None


def camera_regime(sess, site: str):
    """The march regime (axis, sign) of the camera ``sess`` holds, for
    both sessions. A camera that arrived as host floats is decided from
    them (counter ``regime_host``): nothing is read from the device. Any
    other camera goes through `choose_axis`, which reads eye and target
    back — a device->host read that waits for the device where the
    leaves are new there (after an orbit step), a span of its own so
    that a trace can say what it costs (and no span at all in a run that
    records nothing: this is every frame's path)."""
    pose = host_pose(sess)
    if pose is not None:
        sess.obs.count("regime_host")
        return sess._slicer.march_axis(pose.eye, pose.target)
    if not sess.obs.enabled:
        return sess._slicer.choose_axis(sess.camera)
    with sess.obs.span("camera_readback", frame=sess.frame_index,
                       site=site):
        return sess._slicer.choose_axis(sess.camera)


def drain_steering(sess, launch=None) -> None:
    """Apply all pending steering messages to ``sess``. Shared by
    InSituSession and SceneSession so the steering protocol has ONE
    consumer (`steer_session`). ``launch``: a recorded caller's
    ``prev_ready`` reader, asked as the ``steer`` span opens.

    A camera message is the one request a viewer sends, so each one
    applied takes the next number (``sess._steer_seq``): the frames
    rendered from it carry that number on their spans, and a recorded
    run's ``steer`` span says how many messages it drained (``msgs``),
    the newest number it applied (``seq``) and when, on
    ``time.perf_counter``'s own scale (``t_drain``: what a viewer that
    stamped its send on the same clock measures the queueing against)."""
    if sess.steering is None:
        return
    rec = sess.obs.enabled
    with sess.obs.span("steer", frame=sess.frame_index) as span:
        if launch is not None:
            span.note(prev_ready=launch())
        msgs, seq0, t_drain = 0, sess._steer_seq, None
        for msg in sess.steering.drain():
            if rec and t_drain is None:
                t_drain = time.perf_counter()
            msgs += 1
            steer_session(sess, msg)
            if msg.get("type") == "camera":
                sess._steer_seq += 1
        if rec:
            found = {"msgs": msgs}
            if msgs:
                found["t_drain"] = t_drain
            if sess._steer_seq > seq0:
                found["seq"] = sess._steer_seq
            span.note(**found)


def apply_tf_steering(sess, msg: dict, invalidate) -> None:
    """Shared handler for 'tf' steering messages (the reference's
    updateVis TF path, DistributedVolumeRenderer.kt:747-774): swap
    ``sess.tf`` and call ``invalidate()`` to drop the compiled steps that
    baked the old TF in as constants. Malformed payloads are logged and
    IGNORED — the steering socket is network-facing, and a buggy viewer
    must not be able to kill an in-situ run mid-simulation."""
    if msg.get("type") != "tf":
        return
    from scenery_insitu_tpu.runtime.streaming import tf_from_message

    try:
        tf = tf_from_message(msg)
    except Exception as e:
        sess.log(f"ignoring malformed tf steering message: {e!r}")
        return
    sess.tf = tf
    invalidate()


def _tf_fingerprint(tf) -> str:
    """Content identity of a TransferFunction (knot arrays hashed) —
    the recompile-or-reuse cache key of steered TF updates
    (docs/SCENARIOS.md "Steered transfer functions"): two messages
    describing the same polyline map to the same compiled steps."""
    import hashlib

    h = hashlib.sha1()
    for leaf in tf:
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()[:16]


def regime_camera(cam0, regime, slicer_mod):
    """Synthetic camera guaranteed to resolve to ``regime`` under
    choose_axis: eye on the regime's axis at the original distance with a
    small off-axis bias (stable argmax, up never parallel). ONE
    implementation for every prewarm path — the synthesis must stay in
    lockstep with choose_axis's convention. Raises on invalid regimes
    (also the only validation of caller-supplied tuples)."""
    a, s = regime
    if a not in (0, 1, 2) or s not in (1, -1):
        raise ValueError(f"invalid march regime {regime!r} "
                         "(expected (axis in 0..2, sign ±1))")
    eye = np.asarray(cam0.eye, np.float64)
    tgt = np.asarray(cam0.target, np.float64)
    dist = float(np.linalg.norm(eye - tgt)) or 2.5
    off = np.full(3, 0.2 * dist)
    off[a] = 0.0
    new_eye = tgt.copy() - off
    new_eye[a] = tgt[a] - s * dist
    cam = cam0._replace(eye=jnp.asarray(new_eye, jnp.float32))
    if slicer_mod.choose_axis(cam) != (a, s):
        # loud, -O-proof: a step compiled under a mislabeled regime key
        # would silently poison the cache and the prewarm timings
        raise RuntimeError(
            f"regime_camera drifted from choose_axis for {regime!r}")
    return cam


class _RegimeMode(NamedTuple):
    """What a mode that compiles per march regime contributes to
    `InSituSession._regime_frame`."""
    what: str           # the `compile` event's `what`
    site: str           # the `camera_readback` span's `site`
    prefix: tuple       # of the table key, before (axis, sign)
    entry: Callable     # AxisSpec -> StepEntry
    frame: Callable     # (key, entry) -> (out, meta or None)


def advance_camera_and_index(sess) -> None:
    """Benchmark-orbit the camera (if enabled) and bump the frame index."""
    if sess.orbit_rate:
        sess.camera = orbit(sess.camera, jnp.float32(sess.orbit_rate))
    sess.frame_index += 1


def _sharded_sim(mesh) -> bool:
    """Does the sim state live z-sharded on ``mesh``? On a multi-rank mesh
    it does: the jitted advance keeps the sharding (the fused Gray-Scott
    kernel takes its z halos from the ring neighbours; stencil rolls
    lower to halo collectives), so the render step's z-sharded input is
    the state's own field, not a per-frame scatter from the first
    device."""
    return mesh is not None and mesh.devices.size > 1


def _field_sharding(mesh, axis=None, ndim: int = 3):
    """Where a sim field f32[..., D, H, W] lives on a multi-rank mesh:
    z-sharded over the flat rank axis, as `shard_volume` shards the
    rendered field."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    axis = axis or mesh.axis_names[0]
    return NamedSharding(mesh, P(*([None] * (ndim - 3)
                                   + [axis, None, None])))


def _place_sim_state(state, mesh, axis=None):
    """Place a volume-sim state pytree on a multi-rank mesh: fields
    (f32[D, H, W], f32[3, D, H, W]) z-sharded like `shard_volume` shards
    the rendered field, everything smaller (parameters, tracers)
    replicated — the shardings the jitted advance hands back, so frame 1
    does not recompile it for a changed input placement."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def place(x):
        return jax.device_put(x, _field_sharding(mesh, axis, x.ndim)
                              if x.ndim >= 3 else NamedSharding(mesh, P()))

    return jax.tree_util.tree_map(place, state)


class VolumeSimAdapter:
    """Uniform facade over the built-in volume sims (kind -> state/advance/
    field). ``mesh``/``axis``: the session's mesh and flat rank axis (see
    `_sharded_sim`); ``obs``: the session's recorder, for the scope table
    of a sim program that has phases inside."""

    def __init__(self, cfg: FrameworkConfig, seed: int = 0, mesh=None,
                 axis=None, obs=None):
        kind = cfg.sim.kind
        self.kind = kind
        sharded = _sharded_sim(mesh)
        if kind == "gray_scott":
            # born z-sharded where the state lives so (`_seed_cubes`):
            # no device ever holds more of the start than its own slab
            st = self._built(lambda: gs.GrayScott.from_config(
                cfg.sim, seed=seed,
                sharding=_field_sharding(mesh, axis) if sharded else None),
                mesh, axis, obs)
            # fused_stencil routes through the time-fused Pallas kernel
            # on TPU (T steps per HBM round trip of u, v), which reads
            # from the state's placement whether its z halos are the
            # buffer's own wrap or the ring neighbours' planes; off-TPU
            # or with the flag off it is exactly the XLA roll path
            step = (gs.multi_step_fast if cfg.sim.fused_stencil
                    else gs.multi_step)
            # the rendered field is a leaf of the state (v): nothing to
            # compute, so nothing is kept beside it
            self._advance = lambda s, n: (step(s, n), None, None)
            self._render = lambda s: (s, s.field)
        elif kind == "vortex":
            st = self._built(lambda: vx.VortexFlow.init_ring(
                tuple(cfg.sim.grid), vx.VortexParams.create(dt=cfg.sim.dt)),
                mesh, axis, obs)
            # ONE program per frame hands back (u, field, windows) in
            # the placements it took: n steps, then |curl u| and its
            # normalisation, and what each step's back-trace read
            # (sim/vortex.frame_program); a recording session keeps its
            # `sim_*` scope table
            frame = (vx.frame_program(mesh, axis or mesh.axis_names[0])
                     if sharded else vx.frame_program())

            def through(program):
                def run(s, n):
                    u, field, windows = program(s.u, s.params, n)
                    return s._replace(u=u), field, windows
                return run

            self._advance = through(scoped_step(frame, obs)
                                    if obs is not None else frame)
            # of a state no frame has advanced: 0 steps, the field alone
            # (around the wrapper, whose table is the frame's program's)
            self._render = lambda s: through(frame)(s, 0)[:2]
        else:
            raise ValueError(f"unknown volume sim kind {cfg.sim.kind!r}")
        self._rec = obs
        # the vortex frames' `windows` that nobody has read yet, oldest
        # first, and whether a step's window has given way before
        self._windows, self._gave_way = deque(), False
        self.state = st

    @staticmethod
    def _built(make, mesh, axis, rec):
        """The start ``make()`` builds, placed where the state lives
        (`_place_sim_state`: a no-op for leaves that were born there),
        under the `sim.build` span: attrs ``devices`` and
        ``bytes_per_device`` (the largest device's share of the leaves),
        counter ``sim_state_shards_built`` (field shards placed)."""
        with (rec.span("sim.build", frame=0) if rec is not None
              else contextlib.nullcontext()) as span:
            st = make()
            if _sharded_sim(mesh):
                st = _place_sim_state(st, mesh, axis)
            if rec is not None and rec.enabled:
                held, shards = {}, 0
                for x in jax.tree_util.tree_leaves(st):
                    if x.ndim >= 3:
                        shards += len(x.addressable_shards)
                    for sh in x.addressable_shards:
                        held[sh.device] = (held.get(sh.device, 0)
                                           + sh.data.nbytes)
                span.note(devices=len(held),
                          bytes_per_device=max(held.values()))
                rec.count("sim_state_shards_built", shards)
        return st

    @property
    def state(self):
        return self._state

    @state.setter
    def state(self, st) -> None:
        # whoever replaces the state (a restore, a seeded start) also
        # drops the field that was rendered from the old one
        self._state, self._field = st, None

    def advance(self, n: int) -> None:
        self._state, self._field, windows = self._advance(self._state, n)
        if windows is not None:
            self._windows.append(windows)
            self.poll()

    def poll(self, wait: bool = False) -> None:
        """Account for the vortex steps whose ``windows`` the device has
        written — with ``wait`` for all of them — and never sync
        otherwise: a step that asked for the whole field where the
        program holds a window mints one ``sim.vortex_window`` ledger
        row, and a recording session counts the steps by the branch
        they took."""
        while self._windows and (wait or self._windows[0].is_ready()):
            steps = np.asarray(self._windows.popleft())
            held = steps[steps[:, 1] > 0]
            for reach, halo, _ in held[held[:, 2] == 0]:
                _obs.degrade(
                    "sim.vortex_window", "windowed", "whole_field",
                    f"a step reaches {reach:.4f} voxels in z; the "
                    f"window's halo of {int(halo)} planes serves a reach "
                    f"under {int(halo) - 1}", warn=not self._gave_way)
                self._gave_way = True
            if self._rec is not None and len(held):
                windowed = int(held[:, 2].sum())
                self._rec.count("sim.vortex_window.windowed", windowed)
                self._rec.count("sim.vortex_window.whole_field",
                                len(held) - windowed)

    def close(self) -> None:
        """The session's end: the steps not yet accounted for are."""
        self.poll(wait=True)

    @property
    def field(self) -> jnp.ndarray:
        if self._field is None:
            self._state, self._field = self._render(self._state)
        return self._field


class DatasetVolumeAdapter:
    """A volume source with no simulation behind it: a raw dataset file
    (≅ VolumeFromFileExample, `fromPathRaw` x num_parts) loaded in
    z-slabs straight to the device at the FILE'S dtype and held
    resident, unchanged, for the session's life. ``advance`` does
    nothing and ``field`` is that array: u8 (or u16) in HBM, normalized
    where the march reads it (`core.volume.value_scale`), never widened.

    By default the file is ``<runtime.data_dir>/<runtime.dataset>.raw``
    with the dims and dtype the tables of `core.volume` give that name;
    ``dims_xyz`` / ``dtype`` say otherwise (a small file of a test);
    ``parts`` is the number of z-slabs it is read and put in.
    ``static`` tells the session that what depends on the field alone
    (the occupancy ranges of a march regime) is computed once. The load
    happens at the first use of ``field`` — for a session, inside its
    constructor, under its recorder: span ``dataset.load`` (attrs
    ``name``, ``dims``, ``dtype``, ``parts``, ``bytes``, ``read_s``,
    ``put_s``), counter ``volume_resident_bytes``."""

    kind = "dataset"
    static = True

    def __init__(self, cfg: FrameworkConfig, mesh=None, axis=None,
                 dims_xyz=None, dtype=None, parts: int = 8):
        from scenery_insitu_tpu.core import volume as _vol

        rt = cfg.runtime
        self.name = rt.dataset
        self.path = os.path.join(rt.data_dir, f"{rt.dataset}.raw")
        self.dims_xyz = tuple(dims_xyz
                              or _vol.DATASET_DIMS_XYZ[rt.dataset.lower()])
        self.dtype = np.dtype(dtype
                              or _vol.DATASET_DTYPES[rt.dataset.lower()])
        self._parts = parts
        self._place = (_field_sharding(mesh, axis) if _sharded_sim(mesh)
                       else None)
        self._field = None

    def advance(self, n: int) -> None:
        """No simulation: the field is the file's, whatever ``n``."""

    @property
    def field(self) -> jnp.ndarray:
        if self._field is None:
            from scenery_insitu_tpu.core.volume import load_raw_parts

            rec = _obs.get_recorder()
            with rec.span("dataset.load", frame=0) as span:
                took = {}
                field = load_raw_parts(self.path, self.dims_xyz,
                                       self.dtype, self._parts,
                                       timings=took)
                if self._place is not None:
                    field = jax.device_put(field, self._place)
                held = max(sh.data.nbytes
                           for sh in field.addressable_shards)
                span.note(name=self.name, dims=list(self.dims_xyz),
                          dtype=self.dtype.name, parts=took["parts"],
                          bytes=field.nbytes, read_s=took["read"],
                          put_s=took["put"])
                rec.count("volume_resident_bytes", held)
            self._field = field
        return self._field


class ParticleSimAdapter:
    """Session facade over the built-in particle sims (lennard_jones | sho;
    ≅ the reference's MD-driven InVisRenderer path and the SHO workload of
    its shm producer, shm_mpiproducer.cpp:85-122)."""

    def __init__(self, cfg: FrameworkConfig, seed: int = 0):
        from functools import partial

        from scenery_insitu_tpu.sim import particles as pt

        kind = cfg.sim.kind
        self.kind = kind
        n = cfg.sim.num_particles
        if kind == "lennard_jones":
            self.state, params, spec = pt.lj_init(n, seed=seed)
            self._advance = partial(pt.lj_multi_step, params=params,
                                    spec=spec)
        elif kind == "sho":
            self.state, params = pt.sho_init(n, seed=seed)

            @partial(jax.jit, static_argnames="n")
            def sho_multi(s, n):
                return jax.lax.fori_loop(
                    0, n, lambda _, st: pt.sho_step(st, params), s)

            self._advance = sho_multi
        else:
            raise ValueError(f"unknown particle sim kind {kind!r}")

    def advance(self, n: int) -> None:
        self.state = self._advance(self.state, n=n)

    @property
    def pos(self) -> jnp.ndarray:
        return self.state.pos

    @property
    def vel(self) -> jnp.ndarray:
        return self.state.vel


class HybridSimAdapter:
    """Vortex flow + passive tracers for the hybrid session mode
    (BASELINE.md Config 5). ``mesh``/``axis``: see `_sharded_sim` — the
    flow is z-sharded, the tracers stay whole."""

    def __init__(self, cfg: FrameworkConfig, seed: int = 0, mesh=None,
                 axis=None):
        grid = tuple(cfg.sim.grid)
        self.kind = "hybrid"
        self.flow = vx.VortexFlow.init_ring(
            grid, vx.VortexParams.create(dt=cfg.sim.dt))
        self.tracers = vx.seed_tracers(grid, cfg.sim.num_particles,
                                       seed=seed)
        if _sharded_sim(mesh):
            self.flow, self.tracers = _place_sim_state(
                (self.flow, self.tracers), mesh, axis)

        @jax.jit
        def _adv(u, pos, n):
            params = self.flow.params

            def body(_, carry):
                fl, p = carry
                p = vx.advect_tracers(fl.u, p, params.dt)
                return vx.step(fl), p

            fl, p = jax.lax.fori_loop(0, n, body,
                                      (vx.VortexFlow(u, params), pos))
            return fl.u, p

        self._adv = _adv

    def advance(self, n: int) -> None:
        u, self.tracers = self._adv(self.flow.u, self.tracers,
                                    jnp.int32(n))
        self.flow = self.flow._replace(u=u)

    @property
    def field(self) -> jnp.ndarray:
        return self.flow.field


def _no_span(name, **_):
    return contextlib.nullcontext()


def _holders(bufs: list, i: int) -> int:
    return sys.getrefcount(bufs[i])


# what `_holders` reads of a buffer that only its list holds
_UNHELD = _holders([object()], 0)


class HostFrames:
    """Assembles frames that are sharded over the mesh into host arrays,
    and uses an array again once nobody else holds it.

    On a v5e's host the first touch of a fresh 157 MB array's pages is
    nine tenths of a serial assembly (264 ms against 22 into pages
    already touched), and sixteen threads copying disjoint blocks make
    that 112 and 8 (PERF.md, PR 27; numpy releases the GIL in a
    plain-dtype copy). A sink may keep what it was handed for as long as
    it likes: an array is taken again only when this list holds its one
    remaining reference, and every numpy view, slice or buffer export of
    an array that owns its data holds one (numpy collapses a view's
    ``base`` to the owner). A frame that a sink does keep costs one fresh
    array, which the threads keep inside a four-rank frame's time.

    The SHARDS it copies from are the runtime's own host buffers, and
    since PR 45 those and this pool's own ``np.empty`` come from a heap
    that keeps its large blocks wherever the frame weighs 32 MB or more
    (`InSituSession._keep_host_heap`, runtime/hostheap.py), so neither
    side of the copy meets an untouched page after the first frames.
    Whether ``take`` still needs its pool then is a question for a
    `simplicity` PR; it is kept as it was.

    What it copies depends on how the frame left the mesh. A VDI frame
    leaves sharded over its LEADING (slot) axis wherever the step can
    re-shard it (parallel/pipeline.py ``_frame_out``): each shard is one
    contiguous piece of the whole, and the threads' tasks are whole
    slots of it (6.5 MB each at 640 x 640). A frame sharded over its
    minor axis (a plain image; a VDI whose slots the ranks do not
    divide) is W/n-wide rows between the other shards' rows, the same
    assignments at a fraction of the rate (157 MB as 640 B runs: 9 GB/s
    on a v5e's host, PERF.md, PR 40)."""

    WORKERS = 16

    def __init__(self):
        self._bufs: list = []
        self._threads = None        # started by the first sharded frame
        self.last_fresh = False     # the last `take` had to allocate

    def take(self, shape, dtype) -> np.ndarray:
        """A writeable C-contiguous array of this shape and dtype whose
        contents are undefined. Free arrays it does not hand out are let
        go — the others of this size at once, those of another size when
        it has to allocate — so the list never holds more than the frames
        alive at one time. ``last_fresh`` says whether it allocated (the
        ``fresh`` of a recorded run's ``fetch.concat`` span)."""
        bufs = self._bufs
        free = [i for i in range(len(bufs)) if _holders(bufs, i) == _UNHELD]
        same = [i for i in free
                if bufs[i].shape == shape and bufs[i].dtype == dtype]
        drop = set(same[1:] if same else free)
        self._bufs = [b for i, b in enumerate(bufs) if i not in drop]
        self.last_fresh = not same
        if same:
            buf = bufs[same[0]]
            buf.flags.writeable = True
            return buf
        buf = np.empty(shape, dtype)
        self._bufs.append(buf)
        return buf

    def assemble(self, shape, dtype, parts) -> np.ndarray:
        """The read-only array of ``shape`` whose block ``index`` holds
        ``block`` for each ``(index, block)`` of ``parts`` (disjoint, and
        together the whole array): the slice assignments ``np.asarray``
        makes of a sharded ``jax.Array``, each cut along its leading axis
        so that all the threads have one."""
        whole = self.take(shape, dtype)
        if self._threads is None:
            self._threads = ThreadPoolExecutor(
                self.WORKERS, thread_name_prefix="sitpu-fetch")
        cuts = max(1, self.WORKERS // len(parts))
        dsts, srcs = [], []
        for index, block in parts:
            dst = whole[index]
            step = -(-len(block) // cuts)
            for k in range(0, len(block), step):
                dsts.append(dst[k:k + step])
                srcs.append(block[k:k + step])
        for _ in self._threads.map(np.copyto, dsts, srcs):
            pass                    # reading each result raises its error
        whole.flags.writeable = False       # as np.asarray's is
        return whole


class InSituSession:
    def __init__(self, cfg: Optional[FrameworkConfig] = None,
                 mesh=None, camera: Optional[Camera] = None,
                 tf: Optional[TransferFunction] = None,
                 sim: Optional[VolumeSimAdapter] = None,
                 sinks: Sequence[Sink] = (), log=None):
        self.cfg = cfg or FrameworkConfig()
        self.log = log or (lambda s: None)
        if mesh is not None:
            self.mesh = mesh
        else:
            # mesh topology is first-class (docs/MULTIHOST.md): a
            # hierarchical TopologyConfig builds the 2-D (hosts, ranks)
            # mesh and the distributed steps composite in two levels.
            # Particle sessions composite sort-first (all_gather +
            # depth-min) — no sort-last exchange to split — so a
            # hierarchy request there is inert, ledgered, and the flat
            # mesh renders
            topo_cfg = self.cfg.topology
            particles = (isinstance(sim, ParticleSimAdapter)
                         or (sim is None and self.cfg.sim.kind
                             in ("lennard_jones", "sho")))
            if particles and topo_cfg.num_hosts > 1:
                _obs.degrade(
                    "topology.hier", f"num_hosts={topo_cfg.num_hosts}",
                    "flat", "particle sessions composite sort-first — "
                    "no two-level sort-last composite to run", warn=False)
                topo_cfg = None
            self.mesh, _ = make_topology_mesh(topo_cfg, self.cfg.mesh)
        # the flat axis view + total rank count every mesh consumer uses
        # (a plain name on 1-D meshes, the (hosts, ranks) tuple on 2-D)
        self._flat_axis, self._n_ranks, self._topo = resolve_mesh_topology(
            self.mesh, topology=(self.cfg.topology
                                 if len(self.mesh.axis_names) > 1
                                 else None))
        # the recorder wraps+subsumes the per-phase Timers: every span
        # feeds `self.timers` (same PhaseStats/windowed dumps as before),
        # and with obs enabled also records structured frame/rank events
        self.obs = _obs.Recorder.from_config(
            self.cfg.obs, rank=jax.process_index(), log=self.log,
            window=self.cfg.runtime.stats_window)
        self.timers = self.obs.timers
        # ALWAYS take over the process slot (enabled or not): the
        # library-level span/degrade sites route through get_recorder(),
        # and a stale enabled recorder from a finished session would
        # otherwise keep absorbing this session's events
        _obs.set_recorder(self.obs)
        # live SLO engine (docs/OBSERVABILITY.md "SLO engine"): rolling
        # p50/p99 over frame latency + per-phase budgets, checked on the
        # loop; session.slo.snapshot() is the health signal
        from scenery_insitu_tpu.obs.slo import SLOEngine
        self.slo = SLOEngine(self.cfg.slo, recorder=self.obs)
        # fleet telemetry side-channel (docs/OBSERVABILITY.md "Fleet
        # tracing"): obs.collector configured -> batched event publish
        # on the frame loop, non-blocking, drops ledgered
        self._obs_pub = None
        if self.cfg.obs.collector:
            from scenery_insitu_tpu.obs.collector import ObsPublisher
            self._obs_pub = ObsPublisher(
                self.cfg.obs.collector, self.cfg.obs.collector_hb,
                rank=self.obs.rank,
                interval_s=self.cfg.obs.collector_interval_s)
        if sim is not None:
            self.sim = sim
        elif self.cfg.sim.kind in ("lennard_jones", "sho"):
            self.sim = ParticleSimAdapter(self.cfg)
        elif self.cfg.sim.kind == "hybrid":
            self.sim = HybridSimAdapter(self.cfg, mesh=self.mesh,
                                        axis=self._flat_axis)
        elif self.cfg.runtime.dataset.lower() in DATASET_DIMS_XYZ:
            # a name of the raw-file table: no simulation, the file
            self.sim = DatasetVolumeAdapter(self.cfg, mesh=self.mesh,
                                            axis=self._flat_axis)
        else:
            self.sim = VolumeSimAdapter(self.cfg, mesh=self.mesh,
                                        axis=self._flat_axis, obs=self.obs)
        self.tf = tf or for_dataset(
            self.cfg.sim.kind if self.cfg.runtime.dataset == "procedural"
            else self.cfg.runtime.dataset)
        self.camera = camera or Camera.create(
            (0.0, 0.6, 3.0), fov_y_deg=50.0, near=0.3, far=20.0)
        self._host_pose = None      # set by steer_session
        self.sinks: List[Sink] = list(sinks)
        # session failure isolation (docs/ROBUSTNESS.md): every frame
        # sink, tile sink and on_steer callback runs behind this guard —
        # one failing fault.max_sink_failures consecutive times is
        # quarantined (session.sink ledger) instead of killing the run
        self._sink_guard = SinkGuard(self.cfg.fault.max_sink_failures,
                                     log=self.log)
        # tile-granular delivery (docs/PERF.md "Tile waves"): with
        # composite.schedule == "waves" every VDI frame is also split
        # into its n_ranks * wave_tiles column-block tiles and each tile
        # payload ({vdi_color, vdi_depth, tile, tiles, col0, frame,
        # meta}) is handed to these sinks IN COLUMN ORDER before the
        # frame sinks see the assembled frame — subscribers (e.g.
        # streaming.stream_tile_sink) start decoding the first columns
        # while later tiles are still being fetched
        self.tile_sinks: List[Sink] = []
        # the asynchronous delivery plane (docs/PERF.md "Async
        # delivery"): delivery.enabled moves the post-fetch sink work
        # (tile payloads in column order, then the frame sinks) onto a
        # background worker draining a bounded FIFO, so steady-state
        # frame time is max(device, host) instead of device + host. The
        # executor shares the SinkGuard and the LIVE sink lists above;
        # run()/teardown drain it so no fetched frame is lost.
        self._delivery = None
        if self.cfg.delivery.enabled:
            from scenery_insitu_tpu.runtime.delivery import (
                DeliveryExecutor)
            self._delivery = DeliveryExecutor(
                self.cfg.delivery, self._sink_guard, self.tile_sinks,
                self.sinks, recorder=self.obs, slo=self.slo,
                log=self.log)
        self.frame_index = 0
        # render rebalancing (docs/PERF.md "Render rebalancing"): the
        # current planned z-band depths per rank (None = even split) and
        # the frame of the last host-side re-plan; see _maybe_replan.
        # rebalance="bricks" keeps a BrickMap instead (docs/SCENARIOS.md
        # "Brick maps": non-convex brick→rank assignment, re-planned by
        # brick-stealing)
        self._plan = None
        self._bricks = None
        self._plan_frame = None
        self._steps = StepTable(self.obs)
        self.orbit_rate = 0.0  # radians/frame camera sweep (benchmark mode)
        self.steering = None   # optional streaming.SteeringEndpoint
        self.on_steer: List[Callable[[dict], None]] = []  # non-camera msgs
        # frame index -> (VDIMetadata at dispatch, the number of the
        # newest camera message that frame was rendered from, a recorded
        # VDI step's slot-row account: `_count_fold_slots`)
        self._pending_meta = {}
        self._fold_slots = None     # the step just dispatched said this
        self._steer_seq = 0     # camera messages applied (drain_steering)
        self._pending = deque()     # run()'s in-flight frames, newest last
        self._host_frames = HostFrames()    # see _to_host
        self._heap_kept = None      # decided by the first frame fetched

        from scenery_insitu_tpu.ops import slicer as _slicer
        self._slicer = _slicer
        self.engine = _slicer.resolve_engine(self.cfg.slicer.engine)
        self._build_steps()
        # runtime TF updates (the reference's updateVis TF payload):
        # rebuild the compiled steps — the TF is baked in as constants
        self.on_steer.append(self._apply_tf_message)

        # world placement: sim grid centered, largest side = 2 world units
        if self.mode == "particles":
            # particle box [0, box) is rendered centered by the step itself
            d = h = w = 1
            self._origin = jnp.zeros((3,), jnp.float32)
            self._spacing = jnp.ones((3,), jnp.float32)
        else:
            d, h, w = (np.asarray(self.sim.field.shape)
                       if sim is not None
                       or isinstance(self.sim, DatasetVolumeAdapter)
                       else tuple(self.cfg.sim.grid))
            vox = 2.0 / max(d, h, w)
            self._origin = jnp.asarray(
                [-w * vox / 2, -h * vox / 2, -d * vox / 2], jnp.float32)
            self._spacing = jnp.full((3,), vox, jnp.float32)
            cc = self.cfg.composite
            if cc.rebalance == "bricks" and cc.rebalance_bricks \
                    and int(d) % cc.rebalance_bricks:
                # impossible geometry must fail at session build, not
                # minutes in at the first replan (BrickMap would reject
                # it there; the knob is the fix to name)
                raise ValueError(
                    f"composite.rebalance_bricks={cc.rebalance_bricks} "
                    f"does not divide the volume depth {int(d)} (use 0 "
                    f"for auto, or a divisor)")

    def _build_steps(self) -> None:
        """Resolve the mode for the current sim/engine and forget every
        compiled step and all carried state (`StepTable.reset`). Called
        at construction, after a render re-plan, and after a runtime
        transfer-function change not seen before (TF, plan and brick map
        are compile-time constants of every step). A mode that compiles
        per march regime only names its parts here (``_regime_mode``);
        `_regime_frame` builds its steps as the camera reaches them."""
        from scenery_insitu_tpu.parallel import pipeline

        r = self.cfg.render
        # step-cache rebuilds drop every compiled executable — counted so
        # a trace can attribute a mid-run compile stall (e.g. a TF
        # steering update) to its cause
        self.obs.count("build_steps")
        table = self._steps
        table.reset()
        self._tf_key = _tf_fingerprint(self.tf)
        self.mode = "vdi"
        self._regime_mode = None
        if isinstance(self.sim, ParticleSimAdapter):
            # sort-first sphere rendering (≅ InVisRenderer + Head)
            from scenery_insitu_tpu.parallel.particles import (
                distributed_particle_step)
            self.mode = "particles"
            table.fixed = scoped_step(distributed_particle_step(
                self.mesh, r.width, r.height,
                radius=self.cfg.sim.particle_radius), self.obs)
        elif isinstance(self.sim, HybridSimAdapter):
            # hybrid is implemented on the slice-march engine only (the
            # particle layer shares the virtual camera's rays); the engine
            # knob is overridden so telemetry reports what actually runs
            self.mode = "hybrid"
            self.engine = "mxu"
            self._regime_mode = _RegimeMode(
                "hybrid_step", "hybrid", ("hybrid",), self._hybrid_entry,
                self._hybrid_frame)
        elif self.cfg.runtime.generate_vdis and self.engine == "mxu":
            self._regime_mode = _RegimeMode(
                "vdi_step", "mxu_step", (), self._vdi_entry,
                self._vdi_frame)
        elif self.cfg.runtime.generate_vdis:
            table.fixed = scoped_step(pipeline.distributed_vdi_step(
                self.mesh, self.tf, r.width, r.height,
                self.cfg.vdi, self.cfg.composite, max_steps=r.max_steps,
                **self._decomp()), self.obs)
        elif self.engine == "mxu":
            # TPU plain mode: slice march + column exchange + nearest-first
            # composite on the intermediate grid, homography-warped to the
            # display camera per frame (≅ DistributedVolumeRenderer.kt:
            # 175-189's plain pipeline, re-scheduled for the MXU)
            self.mode = "plain"
            self._regime_mode = _RegimeMode(
                "plain_step", "plain", ("plain",), self._plain_entry,
                self._plain_frame)
        else:
            self.mode = "plain"
            table.fixed = scoped_step(pipeline.distributed_plain_step(
                self.mesh, self.tf, r.width, r.height, r,
                comp_cfg=self.cfg.composite, **self._decomp()), self.obs)

        self._temporal = (self.cfg.vdi.adaptive
                          and self.cfg.vdi.adaptive_mode == "temporal"
                          and self.mode in ("vdi", "hybrid")
                          and self.engine == "mxu")
        # temporal fragment reuse (docs/PERF.md "Temporal deltas"): the
        # carried-state plumbing exists on the MXU VDI step only; other
        # modes' builders (gather/hybrid/plain) ledger the knob inert,
        # and the particle step never consults CompositeConfig at all —
        # say so here rather than silently rendering every frame
        # brick-partitioned marches carry no reuse plumbing — the builder
        # ledgers the inert knob (delta.reuse) when a map is active
        self._reuse = (self.cfg.composite.temporal_reuse == "ranges"
                       and self.mode == "vdi" and self.engine == "mxu"
                       and self._bricks is None)
        if self.cfg.composite.temporal_reuse == "ranges" \
                and not self._reuse and self.mode == "particles":
            _obs.degrade("delta.reuse", "ranges", "off",
                         "particle sessions march no volume fragments",
                         warn=False)
        # particle/plain modes never consult cfg.vdi — only reject the
        # mode that would hit the slicer's temporal-needs-state error at
        # trace time (gather VDI generation)
        if (self.cfg.vdi.adaptive
                and self.cfg.vdi.adaptive_mode == "temporal"
                and not self._temporal and self.mode == "vdi"):
            raise ValueError(
                "adaptive_mode='temporal' is carried threshold state of "
                "the MXU VDI pipeline — this session resolved to mode="
                f"{self.mode!r} engine={self.engine!r}; use 'histogram' "
                "there")

    def _apply_tf_message(self, msg: dict) -> None:
        """'tf' steering: swap the TF and recompile-OR-REUSE (knot
        arrays are fixed-shape, so pipeline shapes never change). Shared
        protocol logic lives in `apply_tf_steering`."""
        apply_tf_steering(self, msg, self._tf_invalidate)

    def _decomp(self) -> dict:
        """The build-time geometry every step builder takes: the planned
        z bands or the brick map of the render decomposition, and the
        mesh topology."""
        return dict(plan=self._plan, bricks=self._bricks,
                    topology=self.cfg.topology)

    def _decomp_key(self):
        """The render-decomposition half of the step-cache key — cached
        steps bake the plan / brick map in as build-time geometry (for
        LOD maps that includes the LEVEL tuple: a level change
        materializes different pooled volumes, so steps compiled for
        one level assignment must never serve another)."""
        return (self._plan,
                None if self._bricks is None
                else (self._bricks.owner, self._bricks.level))

    def _tf_invalidate(self) -> None:
        """Steered-TF recompile-or-reuse keyed on TF identity
        (docs/SCENARIOS.md "Steered transfer functions"): the outgoing
        TF's compiled steps are put aside under its fingerprint
        (`StepTable.swap`), and a steered TF seen before (same knots,
        same render decomposition) takes them back instead of recompiling — a time-varying TF
        schedule cycling through k looks pays k compiles total, not one
        per update. Carried temporal threshold / reuse state re-seeds
        either way (it tracks scene content under the OLD TF)."""
        if self.cfg.lod.enabled:
            # the TF-straddle coarsening gate is TF-dependent: force the
            # level replan to re-run before the next march so a brick
            # whose range straddles a NEW opacity edge refines on the
            # very next frame, never a stale one (render_frame replans
            # before it dispatches; tests/test_lod.py property test)
            self._plan_frame = None
        decomp = self._decomp_key()
        new_fp = _tf_fingerprint(self.tf)
        self.obs.count("tf_updates")
        if self._steps.swap((self._tf_key,) + decomp, (new_fp,) + decomp):
            self._tf_key = new_fp
            self.obs.count("tf_steps_reused")
            self.obs.event("tf_update", frame=self.frame_index,
                           reused=True, key=new_fp)
            return
        self.obs.event("tf_update", frame=self.frame_index, reused=False,
                       key=new_fp)
        _obs.degrade("scenario.tf_update", "compiled steps", "recompile",
                     "a steered transfer function not seen before "
                     "rebuilds the compiled steps (TF knots are "
                     "compile-time constants)", warn=False)
        self._build_steps()

    # ------------------------------------------------------------- frames

    def _shard(self, field: jnp.ndarray) -> jnp.ndarray:
        """The field as the render steps take it: z-sharded over the
        flat rank axis. A placement no-op for the built-in volume sims,
        whose state already lives there (`_sharded_sim`)."""
        return shard_volume(field, self.mesh, self._flat_axis)

    def render_frame(self):
        """Advance the sim and dispatch one render step (device arrays)."""
        rec = self.obs.enabled
        # each span a recorded launch can be held in says whether the
        # device had finished the newest frame in flight as it opened
        # (`_prev_ready`: no read in a run that records nothing)
        drain_steering(self, self._prev_ready if rec else None)
        self._maybe_replan()
        with self.obs.span("sim", frame=self.frame_index,
                           kind=self.sim.kind) as sim:
            if rec:
                sim.note(prev_ready=self._prev_ready())
            self.sim.advance(self.cfg.sim.steps_per_frame)
        # what a recorded launch says of itself: the camera message it
        # renders from, and what was in flight when it was made (two
        # non-blocking reads, neither made in a run that records nothing)
        launch = {"steer_seq": self._steer_seq,
                  "upload_busy": bool(getattr(self.sim, "upload_busy",
                                              False)),
                  "prev_ready": self._prev_ready()} if rec else {}
        with self.obs.span("dispatch", frame=self.frame_index,
                           mode=self.mode, engine=self.engine, **launch):
            meta = None
            if self.mode == "particles":
                from scenery_insitu_tpu.parallel.particles import (
                    shard_particles)
                centered = self.sim.pos - self.sim.state.box / 2.0
                out = self._steps.fixed(
                    shard_particles(centered, self.mesh),
                    shard_particles(self.sim.vel, self.mesh), self.camera)
            elif self._steps.fixed is not None:
                out = self._steps.fixed(*self._field_args())
            else:
                out, meta = self._regime_frame()
            meta = (self.frame_metadata(self.frame_index) if meta is None
                    else meta._replace(index=jnp.int32(self.frame_index)))
        if rec:
            with self.obs.span("upkeep", frame=self.frame_index):
                self._frame_dispatched(meta)
        else:
            self._frame_dispatched(meta)
        return out

    @staticmethod
    def _ready(out) -> bool:
        """Every leaf of ``out`` answers ``is_ready()``; nothing waits."""
        return all(leaf.is_ready() for leaf in
                   jax.tree_util.tree_leaves(out))

    def _prev_ready(self) -> bool:
        """Whether the device has finished the newest frame in flight
        (`_ready`): its device->host copy, started at its dispatch, is
        then under way or done, and the launch that reads this found the
        device idle. False when nothing is in flight."""
        return bool(self._pending) and self._ready(self._pending[-1][1])

    def _beside(self) -> bool:
        """Whether a frame NEWER than the one being fetched still has
        programs in flight (the retired frame has left ``_pending``): a
        transfer that runs now runs beside them."""
        return bool(self._pending) and not self._ready(self._pending[-1][1])

    def _frame_dispatched(self, meta) -> None:
        """The loop's bookkeeping after a dispatch (a recorded run's
        ``upkeep`` span, with `_upkeep`)."""
        # metadata snapshot BEFORE the camera advances (fetch is pipelined
        # one frame behind, so it must not see the next frame's pose)
        self._pending_meta[self.frame_index] = (meta, self._steer_seq,
                                                self._fold_slots)
        self._fold_slots = None
        # bound the dict: the fetch runs at most pipeline_depth frames
        # behind, so any older entry is unreachable — without this, a
        # headless run(fetch=False) loop (which never pops) grows it
        # forever
        for k in [k for k in self._pending_meta
                  if k < self.frame_index
                  - self.cfg.runtime.pipeline_depth]:
            del self._pending_meta[k]
        advance_camera_and_index(self)

    def run(self, frames: int, fetch: bool = True,
            profile_dir: Optional[str] = None) -> dict:
        """Run the loop with one-frame async pipelining; returns last
        fetched payload.

        ``profile_dir``: capture a device-side profiler trace of the run
        (open with xprof/tensorboard) — the per-op/per-phase breakdown the
        host-side timers cannot see because the frame is one fused program
        (the reference logged host-side phase spans instead,
        DistributedVolumeRenderer.kt:622-648; see also
        benchmarks/phase_bench.py for the split-stage numbers)."""
        ctx = (jax.profiler.trace(profile_dir) if profile_dir
               else contextlib.nullcontext())
        depth = self.cfg.runtime.pipeline_depth
        try:
            with ctx:
                # depth-k device->host pipeline (docs/PERF.md "Async
                # delivery"): the deque holds the in-flight device
                # frames, newest last; a frame retires (fetch + sink
                # delivery, device refs dropped) once `depth` newer
                # dispatches are in flight. depth 1 is bitwise the
                # historical one-deep overlap.
                pending = self._pending
                payload = {}
                last = frames - 1
                # read once: in a recorded run every statement of the
                # body is under a leaf span (no span around the whole
                # body: it would cover every idle gap of a trace), in any
                # other run under none
                rec, span = self.obs.enabled, self.obs.span
                pages = host_pages() if rec else None

                def retire() -> None:
                    """Retire the oldest frame in flight. Where that
                    fetches, the payload of the frame before is let go
                    first, by name: a rebind would free it (157 MB at
                    512^3 where no sink kept it) between two spans. A
                    recorded run also lets the retired frame's device
                    arrays go by name (on four chips 1.1 ms a frame), and
                    both ``release`` spans say what the process's memory
                    did meanwhile (``rss_pages``, ``minflt``:
                    obs/hostmem.py)."""
                    nonlocal payload
                    index, consume = pending[0][0], pending[0][2]
                    if not rec:
                        if consume:
                            payload = None
                        payload = self._retire(pending.popleft(), fetch,
                                               payload)
                        return
                    if consume:
                        with span("release", frame=index, bytes=sum(
                                v.nbytes for v in payload.values()
                                if isinstance(v, np.ndarray))) as gone:
                            before = pages.read()
                            payload = None
                            gone.note(**pages.since(before))
                    entry = pending.popleft()
                    payload = self._retire(entry, fetch, payload)
                    with span("release", frame=index, device=True) as gone:
                        before = pages.read()
                        del entry
                        gone.note(**pages.since(before))

                if rec:
                    at_start = pages.read()
                    pages.take_grown()
                for i in range(frames):
                    t_f = time.perf_counter()
                    out = self.render_frame()
                    index = self.frame_index - 1
                    # start the device->host copy at dispatch time, but
                    # only when somebody consumes it (sinks registered,
                    # or the caller-visible payload of the final frame)
                    # — a sink-less run pays no host transfer at all
                    consume = fetch and (
                        bool(self.sinks or self.tile_sinks) or i == last)
                    if consume and rec:
                        with span("host_copy.start", frame=index,
                                  bytes=sum(
                                      leaf.nbytes for leaf in
                                      jax.tree_util.tree_leaves(out))):
                            self._start_host_copy(out)
                    elif consume:
                        self._start_host_copy(out)
                    pending.append((index, out, consume))
                    out = None      # the deque holds the only device ref
                    while len(pending) > depth:
                        retire()
                    if rec:
                        # the pages the iteration touched for the first
                        # time, whoever touched them (the runtime's
                        # transfer threads do under no span of the loop):
                        # from the end of the iteration before to the end
                        # of this one
                        with span("upkeep", frame=index) as kept:
                            self._upkeep(t_f)
                            now = pages.read()
                            found = pages.delta(at_start, now, "_frame")
                            at_start, touched = now, pages.take_grown()
                            kept.note(touched_frame=touched, page=PAGE,
                                      **found)
                            self.obs.count("host_pages_touched", touched)
                            if "minflt_frame" in found:
                                self.obs.count("host_minor_faults",
                                               found["minflt_frame"])
                    else:
                        self._upkeep(t_f)
                while pending:
                    retire()
        except BaseException:
            # flight recorder: an unhandled exception must not lose the
            # final unflushed obs window — drain the delivery queue
            # first (frames the device already paid for), dump, then
            # keep raising
            self._pending.clear()
            if self._delivery is not None:
                self._delivery.drain()
            _obs.flight_flush(self.obs, where="run")
            if self._obs_pub is not None:
                self._obs_pub.pump(self.obs, force=True)
            raise
        # end-of-run teardown: drain the async delivery queue, the final
        # partial window frame_done never reached, the whole-run totals,
        # and the obs sinks
        if self._delivery is not None:
            self._delivery.drain()
        self.timers.dump_totals()
        self.obs.flush()
        if self._obs_pub is not None:
            self._obs_pub.pump(self.obs, force=True)
        # every frame of the run has been fetched or waited for, so what
        # a vortex sim's steps read is there to be accounted for
        poll = getattr(self.sim, "poll", None)
        if poll is not None:
            poll()
        return payload

    def close(self) -> None:
        """The session's end: a sim source that owns a thread or a
        channel (`ingest.shm.ShmVolumeSource`) is closed, and the
        built-in volume sims account for the vortex steps nobody has
        read yet (`VolumeSimAdapter.poll`)."""
        close = getattr(self.sim, "close", None)
        if close is not None:
            close()

    def _upkeep(self, t_f: float) -> None:
        """The end of a loop iteration begun at ``t_f``: the timers'
        window, the SLO engine's sample, the collector's batch (a recorded
        run's ``upkeep`` span, with `_frame_dispatched`)."""
        self.timers.frame_done()
        self.slo.observe("frame_ms", (time.perf_counter() - t_f) * 1e3,
                         frame=self.frame_index - 1)
        if self._obs_pub is not None:
            self._obs_pub.pump(self.obs)

    def _retire(self, entry, fetch: bool, payload: dict) -> dict:
        """Retire one pipelined frame: fetch + deliver it when it has
        consumers, otherwise just pace the loop on its device
        completion. The caller already dropped the deque reference, so
        the frame's device buffers free as soon as this returns — the
        pipeline pins exactly `pipeline_depth` frames of HBM, never
        more."""
        index, out, consume = entry
        if consume:
            return self._fetch(index, out)
        if fetch:
            self._sync_nofetch(index, out)
        return payload

    def _start_host_copy(self, out) -> None:
        """Kick off the device->host transfer of every buffer in ``out``
        without blocking (``copy_to_host_async``): by the time the
        depth-k pipeline retires this frame, the bytes are already on
        the host and ``np.asarray`` is a cheap wrap, not a sync.

        The runtime allocates each buffer's host destination here, on
        the calling thread, and its own threads write it later. Before
        the first frame it fetches, the session therefore looks at what
        that frame weighs on this process's host (`_keep_host_heap`)."""
        leaves = jax.tree_util.tree_leaves(out)
        if self._heap_kept is None:
            self._keep_host_heap(leaves)
        for leaf in leaves:
            leaf.copy_to_host_async()

    def _keep_host_heap(self, leaves) -> None:
        """Decide, once a session, whether this process's heap has to keep
        its large blocks (runtime/hostheap.py) — from the host bytes of
        the frame about to be fetched and from nothing a user sets. A
        frame that reaches glibc's ceiling for heap blocks lands on
        freshly mapped pages every time otherwise (its buffers are over
        the ceiling, or the heap's top is trimmed between frames); a
        smaller one is reused by glibc as it is, and a session that
        fetches nothing never comes here. Engaging is for the whole
        process and for good: one line through ``log``, counters
        ``host_heap_kept`` (1 where engaged, 0 where not) and
        ``host_heap_frame_bytes`` (what decided), recorded or not."""
        nbytes = sum(
            leaf.nbytes if leaf.is_fully_addressable else
            sum(sh.data.nbytes for sh in leaf.addressable_shards)
            for leaf in leaves)
        self._heap_kept = nbytes >= hostheap.DEFAULT_MMAP_THRESHOLD_MAX \
            and hostheap.keep_large_blocks(self.log)
        self.obs.count("host_heap_kept", int(self._heap_kept))
        self.obs.count("host_heap_frame_bytes", nbytes)

    def _count_fold_slots(self, slots) -> None:
        """Add a recorded frame's slot-row account (i32[ranks, 2] of the
        step that rendered it, `_vdi_frame`) to ``fold_slot_rows_merged``
        / ``fold_slot_rows``. Called once the frame's programs are done,
        so the read waits for nothing; None (an unrecorded run, a mode
        that folds no VDI) counts nothing."""
        if slots is not None:
            merged, rows = np.asarray(slots).sum(axis=0)
            self.obs.count("fold_slot_rows_merged", int(merged))
            self.obs.count("fold_slot_rows", int(rows))

    def _sync_nofetch(self, index: int, out) -> None:
        """Retire a pipelined frame nobody consumes: drop its metadata
        snapshot and pace on device completion WITHOUT the device->host
        copy the historical path paid here (``fetch=True`` with no
        sinks used to ``np.asarray`` every frame just to throw the
        bytes away)."""
        _, _, slots = self._pending_meta.pop(index, (None,) * 3)
        with self.obs.span("fetch", frame=index, host_copy=False):
            jax.block_until_ready(out)
            self._count_fold_slots(slots)

    def _to_host(self, index: int, out):
        """``out`` with every leaf a read-only numpy array holding the
        bytes ``np.asarray`` would give, inside the caller's ``fetch``
        span. The one routine for a frame sharded over the mesh, recorded
        or not; a one-device frame comes here only in a recorded run.

        A leaf sharded over the mesh is copied shard by shard (``np.asarray``
        of a shard is a wrap where `_start_host_copy` already landed the
        bytes) and assembled on the host by the slice assignments
        ``np.asarray(leaf)`` would make — by `HostFrames`: into an array
        of an earlier frame rather than a fresh one, on several threads,
        which is what makes the assembly cheap enough that the device
        paces a four-rank frame.

        ``obs.enabled`` only decides whether spans open: ``fetch.ready``
        (``jax.block_until_ready``: the frame's device programs; the one
        extra device wait of a recorded run), ``fetch.copy`` (attr
        ``bytes``; one per shard on a mesh, attr ``shard``; and the
        transfer's own account: ``waited``, the frame's programs were
        still running when ``fetch.ready`` was entered, so its end IS the
        transfer's start and the end of the frame's last ``fetch.copy``
        its end; ``beside0`` / ``beside1``, a newer frame's programs
        were in flight when this copy's wait began / ended: `_beside`),
        and ``fetch.concat`` around the host assembly of each sharded
        leaf (attrs ``bytes``, ``rss_pages`` / ``minflt``, ``fresh``: the
        pool had to allocate, and ``kmajor``: every shard's ``index``
        leaves all axes but the first whole, so the assembly is contiguous
        copies). Counters, recorded or not: ``frames_fetched_sharded``,
        and ``frames_fetched_kmajor`` for a frame whose every sharded
        leaf came that way."""
        if self.obs.enabled:
            span = self.obs.span
            pages = host_pages()
            waited = not self._ready(out)
            with span("fetch.ready", frame=index):
                jax.block_until_ready(out)
            beside = self._beside()

            def copied(copy) -> None:
                """The transfer's own account on a ``fetch.copy`` span
                that has its bytes."""
                nonlocal beside
                before, beside = beside, self._beside()
                copy.note(waited=waited, beside0=before, beside1=beside)
        else:
            span, copied, pages = _no_span, None, None
        leaves, treedef = jax.tree_util.tree_flatten(out)
        sharded = [isinstance(leaf, jax.Array) and leaf.is_fully_addressable
                   and not leaf.is_fully_replicated for leaf in leaves]
        if not any(sharded):
            with span("fetch.copy", frame=index,
                      bytes=sum(leaf.nbytes for leaf in leaves)) as copy:
                host = [np.asarray(leaf) for leaf in leaves]
                if copy is not None:        # a recorded run
                    copied(copy)
            return jax.tree_util.tree_unflatten(treedef, host)
        self.obs.count("frames_fetched_sharded")
        host, kmajor = [], True
        for leaf, split in zip(leaves, sharded):
            if not split:
                with span("fetch.copy", frame=index,
                          bytes=leaf.nbytes) as copy:
                    host.append(np.asarray(leaf))
                    if copy is not None:
                        copied(copy)
                continue
            parts = []
            for sh in leaf.addressable_shards:
                if sh.replica_id == 0:
                    with span("fetch.copy", frame=index, shard=sh.device.id,
                              bytes=sh.data.nbytes) as copy:
                        parts.append((sh.index, np.asarray(sh.data)))
                        if copy is not None:
                            copied(copy)
            # blocks cut along the leading axis alone: each one is a
            # contiguous piece of the whole, not rows of it
            whole = all(cut.indices(size) == (0, size, 1)
                        for ix, _ in parts
                        for cut, size in zip(ix[1:], leaf.shape[1:]))
            kmajor = kmajor and whole
            with span("fetch.concat", frame=index,
                      bytes=leaf.nbytes) as concat:
                before = pages.read() if pages is not None else None
                host.append(self._host_frames.assemble(
                    leaf.shape, leaf.dtype, parts))
                if concat is not None:      # a recorded run
                    concat.note(fresh=self._host_frames.last_fresh,
                                kmajor=whole, **pages.since(before))
        if kmajor:
            self.obs.count("frames_fetched_kmajor")
        return jax.tree_util.tree_unflatten(treedef, host)

    def _fetch(self, index: int, out) -> dict:
        from scenery_insitu_tpu.ops.splat import SplatOutput
        meta, steer_seq, slots = self._pending_meta.pop(index, (None,) * 3)
        if meta is None:
            meta = self.frame_metadata(index)
        tiles = ()
        tiled = bool(self.tile_sinks) \
            and self.cfg.composite.schedule == "waves"
        rec = self.obs.enabled
        with self.obs.span("fetch", frame=index,
                           steer_seq=steer_seq) as fetched:
            before = host_pages().read() if rec else None
            if self._n_ranks > 1 or rec:
                # a frame on a mesh, or a recorded run (the copy, timed)
                out = self._to_host(index, out)
            self._count_fold_slots(slots)
            if isinstance(out, VDI):
                # ONE device->host transfer; the tile delivery below and
                # the frame payload share these buffers (a no-op wrap
                # when _start_host_copy already landed the bytes)
                color = np.asarray(out.color)
                depth = np.asarray(out.depth)
                if tiled:
                    if self._delivery is not None:
                        # async path: slice the tile payloads (views,
                        # no copy) here; the worker delivers them in
                        # the same ascending column order
                        tiles = self._tile_payloads(index, meta,
                                                    color, depth)
                    else:
                        # tile-granular path: each finished column
                        # block is delivered BEFORE the frame payload
                        # is assembled — the frame "closes" (frame
                        # sinks run) only after every tile is already
                        # out the door
                        self._deliver_tiles(index, meta, color, depth)
                payload = {"vdi_color": color, "vdi_depth": depth}
            elif isinstance(out, SplatOutput):
                payload = {"image": np.asarray(out.image),
                           "depth": np.asarray(out.depth)}
            else:
                payload = {"image": np.asarray(out)}
            payload["frame"] = index
            payload["meta"] = meta
            if rec:
                fetched.note(**host_pages().since(before))
        if self._delivery is not None:
            # off the critical path: the worker runs the tile sinks then
            # the frame sinks behind the shared SinkGuard; the loop only
            # pays the enqueue (or backpressure, per overflow policy)
            self._delivery.submit(index, payload, tiles)
        else:
            with self.obs.span("sinks", frame=index, steer_seq=steer_seq):
                self._sink_guard.run(self.sinks, index, payload)
        return payload

    def _tile_payloads(self, index: int, meta, color, depth) -> list:
        """Slice one composited VDI frame into its column-block tile
        payloads, ascending global column order (tile t covers columns
        [t*wb, (t+1)*wb)). Tiles are the wave schedule's unit — n_ranks
        * wave_tiles blocks; a width the tiling does not divide degrades
        to per-rank blocks. Slices are views: no host copy here."""
        n = self._n_ranks
        tiles = n * self.cfg.composite.wave_tiles
        w_total = color.shape[-1]
        if w_total % tiles:
            tiles = n                       # waves degraded to frame
        wb = w_total // tiles
        return [{
            "vdi_color": color[..., t * wb:(t + 1) * wb],
            "vdi_depth": depth[..., t * wb:(t + 1) * wb],
            "frame": index, "tile": t, "tiles": tiles,
            "col0": t * wb, "meta": meta,
        } for t in range(tiles)]

    def _deliver_tiles(self, index: int, meta, color, depth) -> None:
        """Hand every column-block tile of one composited VDI frame to
        the tile sinks, in ascending global column order (the delivery
        contract: tile t arrives before tile t+1 and before the frame's
        own sinks)."""
        for payload in self._tile_payloads(index, meta, color, depth):
            with self.obs.span("tile", frame=index,
                               tile=payload["tile"]):
                self.obs.count("tiles_delivered")
                self._sink_guard.run(self.tile_sinks, index, payload,
                                     kind="tile sink")

    # ------------------------------------------------ render rebalancing

    def _z_bins(self, name: str, per_slab, n_out: int):
        """A GLOBAL per-z-bin reduction of the current field, on the
        device: each rank reduces its even slab in data layout
        (``per_slab(local, nzb)``, one sweep, no permute) and the bins
        concatenate along the mesh axis. The jitted reduction is one of
        the step table's helpers: it goes when the TF or the steps
        change."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from scenery_insitu_tpu.ops import occupancy as _occ

        fn = self._steps.helpers.get(name)
        if fn is None:
            axis = self._flat_axis
            nzb = _occ._cap_divisor(
                int(self.sim.field.shape[0]) // self._n_ranks, 32)
            fn = self._steps.helpers[name] = jax.jit(shard_map(
                lambda local: per_slab(local, nzb), mesh=self.mesh,
                in_specs=P(axis, None, None),
                out_specs=P(axis) if n_out == 1 else (P(axis),) * n_out,
                check_vma=False))
        return fn(self._shard(self.sim.field))

    def _replan_profile(self):
        """The live profile of the current field (host numpy):
        ops/occupancy.z_live_profile per z bin."""
        from scenery_insitu_tpu.ops import occupancy as _occ

        tf = self.tf
        return np.asarray(self._z_bins(
            "profile",
            lambda local, nzb: _occ.z_live_profile(local, tf, nzb=nzb), 1))

    def _replan_ranges(self):
        """The sampled value range of the current field per z bin (host
        numpy, ``(lo, hi)``): `ops/occupancy.z_range_profile`, the LOD
        planner's TF-straddle gate input (docs/PERF.md "LOD marching")."""
        from scenery_insitu_tpu.ops import occupancy as _occ

        lo, hi = self._z_bins(
            "ranges",
            lambda local, nzb: _occ.z_range_profile(local, nzb=nzb), 2)
        return np.asarray(lo), np.asarray(hi)

    def _maybe_replan(self) -> None:
        """Host-side re-plan of the RENDER z decomposition
        (CompositeConfig.rebalance == "occupancy"; docs/PERF.md "Render
        rebalancing"), every ``rebalance_period`` frames: fetch the live
        profile, run ops/occupancy.slice_plan (quantum + hysteresis keep
        the plan stable), and when the plan actually CHANGES, drop the
        compiled steps so the next dispatch rebuilds them on the new
        band split — one recompile per adopted plan, minted on the
        fallback ledger (occupancy.replan) with a ``rebalance_plan``
        event carrying the slice histogram and modeled straggler
        factors."""
        cc = self.cfg.composite
        if self.cfg.lod.enabled and cc.rebalance != "bricks":
            # LOD levels live on the brick map — without the brick
            # partition there is nothing to carry them (configured-but-
            # inert knob: say so once, don't silently render level 0)
            _obs.degrade(
                "lod.inert", "lod", "off",
                f"lod.enabled needs composite.rebalance='bricks' to "
                f"carry levels (got {cc.rebalance!r}); every march "
                "samples at level 0", warn=False)
        if cc.rebalance not in ("occupancy", "bricks"):
            return
        n = self._n_ranks
        # an LOD session replans on a single rank too: a level change
        # alters WHAT that rank marches, not just who marches what
        lod_on = (self.cfg.lod.enabled and cc.rebalance == "bricks"
                  and self.mode == "vdi" and hasattr(self.sim, "field"))
        if self.mode == "particles" or not hasattr(self.sim, "field") \
                or (n == 1 and not lod_on):
            # configured-but-inert knob: say so once instead of silently
            # rendering even splits forever
            _obs.degrade(
                "occupancy.rebalance", "occupancy", "even",
                ("single-rank mesh has one band" if n == 1 else
                 f"mode {self.mode!r} renders no volume field to "
                 "rebalance"), warn=False)
            return
        if cc.rebalance == "bricks" and self.mode != "vdi":
            # only the gather/MXU VDI builders consume a brick map —
            # replanning here would recompile hybrid/plain steps that
            # ledger the map inert and render even slabs regardless
            _obs.degrade(
                "bricks.partition", "bricks", "slabs",
                f"mode {self.mode!r} has no brick march (gather/MXU VDI "
                "steps only); the even z-slab decomposition renders",
                warn=False)
            return
        if self._plan_frame is not None and \
                self.frame_index - self._plan_frame < cc.rebalance_period:
            return
        if cc.rebalance == "bricks":
            self._replan_bricks(cc, n)
            return
        from scenery_insitu_tpu.ops import occupancy as _occ

        d = int(self.sim.field.shape[0])
        with self.obs.span("replan", frame=self.frame_index):
            profile = self._replan_profile()
            even = _occ.even_plan(d, n)
            prev = self._plan if self._plan is not None else even
            plan = _occ.slice_plan(
                profile, d, n, min_depth=cc.rebalance_min_depth,
                quantum=cc.rebalance_quantum, prev=prev,
                hysteresis=cc.rebalance_hysteresis)
        self._plan_frame = self.frame_index
        if plan == prev:
            return                      # stable — nothing recompiles
        self.obs.count("rebalance_replans")
        self.obs.event(
            "rebalance_plan", frame=self.frame_index, plan=list(plan),
            straggler_even=round(_occ.straggler_factor(profile, d, even),
                                 3),
            straggler_planned=round(_occ.straggler_factor(profile, d,
                                                          plan), 3))
        _obs.degrade("occupancy.replan", f"plan{tuple(prev)}",
                     f"plan{tuple(plan)}",
                     "render bands re-planned from fetched live "
                     "fractions; affected steps recompile", warn=False)
        self._plan = plan if plan != even else None
        self._build_steps()

    def _replan_bricks(self, cc, n: int) -> None:
        """Brick-stealing re-plan (CompositeConfig.rebalance == "bricks";
        docs/SCENARIOS.md "Brick maps"): bin the fetched z live profile
        into per-brick work and greedily move at most
        ``rebalance_max_moves`` bricks from the most- to the least-loaded
        rank (parallel.bricks.steal_plan, hysteresis-stable). An adopted
        map change drops the compiled steps exactly like a slab replan;
        a map that converges back to the even-convex assignment restores
        the brickless fast path.

        With ``lod.enabled`` the replan ALSO selects per-brick
        refinement levels (`parallel.lod.select_levels`: screen-space
        error + empty coarsening + hysteresis + the TF-straddle gate)
        and scales the stolen work into level units
        (`parallel.lod.level_work_scale`) — a level-2 brick is ~64x
        cheaper than its level-0 self, and equalizing raw live work
        would re-create the straggler the levels just removed. A level
        change recompiles exactly like an ownership change (the
        `_decomp_key` carries the level tuple)."""
        from scenery_insitu_tpu.parallel import bricks as _bk

        lod = self.cfg.lod
        d = int(self.sim.field.shape[0])
        with self.obs.span("replan", frame=self.frame_index):
            profile = self._replan_profile()
            nb = cc.rebalance_bricks or _bk.auto_nbricks(d, n)
            work = _bk.brick_work(profile, d, nb)
            seed = _bk.BrickMap.contiguous(d, n, nb)
            prev = (self._bricks if self._bricks is not None
                    and self._bricks.nbricks == nb else seed)
            if lod.enabled:
                from scenery_insitu_tpu.core.transfer import opacity_edges
                from scenery_insitu_tpu.parallel import lod as _lod

                lo, hi = self._replan_ranges()
                shp = self.sim.field.shape                  # (D, H, W)
                dims = (int(shp[2]), int(shp[1]), int(shp[0]))
                cam = self.camera
                levels = _lod.select_levels(
                    _lod.per_brick(profile, nb, red="mean"),
                    _lod.per_brick(lo, nb, red="min"),
                    _lod.per_brick(hi, nb, red="max"),
                    opacity_edges(self.tf, lod.tf_edge_eps),
                    dims=dims, origin=np.asarray(self._origin),
                    spacing=np.asarray(self._spacing),
                    eye=np.asarray(cam.eye), fov_y=float(cam.fov_y),
                    height_px=self.cfg.render.height, cfg=lod,
                    prev=(self._bricks.level
                          if self._bricks is not None
                          and self._bricks.nbricks == nb else None))
                prev = prev.with_levels(levels)
                work = work * _lod.level_work_scale(
                    levels, dims, self.cfg.render.width,
                    self.cfg.render.height)
            bm = _bk.steal_plan(prev, work,
                                max_moves=cc.rebalance_max_moves,
                                hysteresis=cc.rebalance_hysteresis)
        self._plan_frame = self.frame_index
        new = None if bm.is_even_convex() else bm
        cur = self._bricks
        if (new is None) == (cur is None) and \
                (new is None or (new.owner == cur.owner
                                 and new.level == cur.level)):
            return                      # stable — nothing recompiles
        self.obs.count("rebalance_replans")
        levels_now = list(bm.level)
        self.obs.event(
            "rebalance_plan", frame=self.frame_index, kind="bricks",
            nbricks=nb, owner=list(bm.owner), level=levels_now,
            max_level=int(max(levels_now)) if levels_now else 0,
            straggler_even=round(_bk.straggler_factor(seed, work), 3),
            straggler_planned=round(_bk.straggler_factor(bm, work), 3))
        _obs.degrade("occupancy.replan",
                     f"bricks{tuple(prev.owner)}",
                     f"bricks{tuple(bm.owner)}",
                     "brick ownership re-planned from fetched live "
                     "fractions; affected steps recompile", warn=False)
        self._bricks = new
        self._build_steps()

    def _note_dirty(self, ru) -> None:
        """Host-side accounting of the reuse carry's LAST decision
        (docs/OBSERVABILITY.md): ``delta_march_skipped`` counts tiles
        whose march never issued, and the per-frame dirty histogram
        event carries the per-rank bits. Reads the INCOMING carry — the
        decision it describes is the previous frame's, which has
        already executed (no extra sync on the in-flight dispatch)."""
        d = np.asarray(ru.dirty)
        if not np.asarray(ru.valid).any():
            return                       # seed state: nothing decided yet
        cc = self.cfg.composite
        n = d.size
        tiles_per_rank = (cc.wave_tiles
                          if cc.schedule == "waves" and n > 1 else 1)
        clean = int((d == 0).sum())
        if clean:
            self.obs.count("delta_march_skipped", clean * tiles_per_rank)
        self.obs.event("delta_dirty_tiles", frame=self.frame_index - 1,
                       dirty=[int(x) for x in d],
                       tiles_per_rank=tiles_per_rank,
                       skipped_tiles=clean * tiles_per_rank,
                       total_tiles=n * tiles_per_rank)

    def prewarm_regimes(self, regimes=None) -> dict:
        """Precompile the distributed MXU step for each (axis, sign) march
        regime BEFORE the camera path reaches it. A regime crossing
        mid-run otherwise stalls on a fresh jit of the whole SPMD frame —
        10-24 s at the 512^3 flagship scale per the round-3 captures —
        inside what should be a steady interactive loop (the reference
        never pays this: GPU raycasting has no march-axis specialization;
        this is the TPU design's one compile-shaped cost, so the session
        must be able to hoist it to startup).

        Renders one throwaway frame per regime with the CURRENT field and
        a synthetic camera on that regime's axis (same distance/target).
        Completely invisible to the loop's own state: the camera,
        temporal-threshold cache and regime-reentry tracker are restored;
        the sim, frame index and sinks are never touched. Modes without
        per-regime compilation (particles, gather engine) return {}.

        regimes: iterable of (axis, sign); default all six.
        Returns {(axis, sign): seconds} (compile + one frame each).
        """
        if self._regime_mode is None:
            return {}
        if regimes is None:
            regimes = [(a, s) for a in (0, 1, 2) for s in (1, -1)]
        cam0 = self.camera
        kept = self._steps.snapshot()
        times = {}
        try:
            for regime in regimes:
                self.camera = regime_camera(cam0, regime, self._slicer)
                t0 = time.perf_counter()
                with self.obs.span("prewarm", frame=self.frame_index,
                                   regime=str(regime)):
                    out, _ = self._regime_frame()
                    jax.block_until_ready(out)
                times[tuple(regime)] = round(time.perf_counter() - t0, 2)
        finally:
            self.camera = cam0
            self._steps.restore(kept)
        return times

    # ------------------------------------------- per-regime compiled steps

    def _regime_frame(self):
        """Dispatch one frame of a mode whose step is compiled per march
        regime (MXU VDI, hybrid, plain MXU; the camera may orbit across
        axis boundaries mid-session): the camera's regime, the table's
        entry for it — built on a miss from the regime's `AxisSpec` —
        and the mode's own call. Returns ``(out, meta)``, ``meta`` None
        where the step gives none."""
        mode = self._regime_mode
        regime = camera_regime(self, mode.site)
        key = mode.prefix + regime
        table = self._steps
        if self._temporal or self._reuse:
            table.enter(key)
        entry = table.steps.get(key)
        if entry is None:
            entry = table.compile(
                key, lambda: mode.entry(self._slicer.make_spec(
                    self.camera, self.sim.field.shape, self.cfg.slicer,
                    axis_sign=regime, multiple_of=self._n_ranks)),
                self.frame_index, mode.what, regime)
        return mode.frame(key, entry)

    def _field_args(self):
        return (self._shard(self.sim.field), self._origin, self._spacing,
                self.camera)

    def _vdi_entry(self, spec) -> StepEntry:
        """The MXU sort-last VDI step for ``spec``'s regime, with the
        seeders of the state it carries: threshold maps in temporal
        mode, marched fragments under ``temporal_reuse`` (bricks turn
        that off at `_build_steps`, so a reuse step never carries a
        brick map)."""
        from scenery_insitu_tpu.parallel import pipeline

        c = self.cfg
        build = (pipeline.distributed_vdi_step_mxu_temporal
                 if self._temporal else pipeline.distributed_vdi_step_mxu)
        ranges = None
        if (getattr(self.sim, "static", False) and spec.skip_empty
                and self._plan is None and self._bricks is None):
            # the field never changes: its occupancy ranges for this
            # regime are computed here, once, and not by every frame
            ranges = tuple(np.asarray(x) for x in
                           pipeline.distributed_volume_ranges_mxu(
                               self.mesh, spec)(*self._field_args()[:3]))
        return StepEntry(
            build(self.mesh, self.tf, spec, c.vdi, c.composite,
                  reuse_tol=c.delta.range_tol, ranges=ranges,
                  slot_counts=self.obs.enabled, **self._decomp()),
            seed_thr=(pipeline.distributed_initial_threshold_mxu(
                self.mesh, self.tf, spec, c.vdi, plan=self._plan,
                bricks=self._bricks) if self._temporal else None),
            seed_reuse=(pipeline.distributed_initial_reuse_mxu(
                self.mesh, self.tf, spec, c.vdi, c.composite,
                plan=self._plan) if self._reuse else None))

    def _vdi_frame(self, key, entry):
        if self._reuse and self.obs.enabled:
            ru = self._steps.reuse.get(key)
            if ru is not None:
                self._note_dirty(ru)
        out = self._steps.run(key, entry, self._field_args())
        if self.obs.enabled:
            # a recorded step hands its fold kernel's slot-row account
            # on, still on the device: read where the frame is fetched
            vdi, meta, self._fold_slots = out
            return vdi, meta
        return out

    def _hybrid_entry(self, spec) -> StepEntry:
        """Distributed hybrid frame: volume VDI + tracers, merged on the
        virtual grid, then warped to the display camera. In temporal
        mode the VDI pass carries per-regime threshold state exactly
        like the plain VDI pipeline."""
        from scenery_insitu_tpu.core.volume import Volume
        from scenery_insitu_tpu.parallel import pipeline

        c, r, slicer = self.cfg, self.cfg.render, self._slicer

        @jax.jit
        def warp(img, field, cam):
            vol = Volume(field, self._origin, self._spacing)
            axcam = slicer.make_axis_camera(vol, cam, spec)
            return slicer.warp_to_camera(img, axcam, spec, cam,
                                         r.width, r.height, r.background)

        return StepEntry(
            pipeline.distributed_hybrid_step_mxu(
                self.mesh, self.tf, spec, c.vdi, c.composite,
                radius=c.sim.particle_radius * float(self._spacing[0]),
                stamp=5, temporal=self._temporal, **self._decomp()),
            seed_thr=(pipeline.distributed_initial_threshold_mxu(
                self.mesh, self.tf, spec, c.vdi, plan=self._plan)
                if self._temporal else None),
            after=warp)

    def _hybrid_frame(self, key, entry):
        from scenery_insitu_tpu.parallel.particles import shard_particles

        field = self.sim.field
        vel = vx.tracer_velocities(self.sim.flow.u, self.sim.tracers)
        world = vx.tracers_to_world(self.sim.tracers, self._origin,
                                    self._spacing)
        sfield = self._shard(field)
        img, meta = self._steps.run(
            key, entry,
            (sfield, self._origin, self._spacing,
             shard_particles(world, self.mesh),
             shard_particles(vel, self.mesh), self.camera),
            seed_args=(sfield, self._origin, self._spacing, self.camera))
        return entry.after(img, field, self.camera), meta

    def _plain_entry(self, spec) -> StepEntry:
        """Distributed plain-image frame on the slice-march engine:
        per-rank `render_slices` + column all_to_all + nearest-first
        composite (one SPMD program per march regime), then the
        homography warp to the display camera."""
        from scenery_insitu_tpu.parallel import pipeline

        c, r, slicer = self.cfg, self.cfg.render, self._slicer

        @jax.jit
        def warp(img, axcam, cam):
            return slicer.warp_to_camera(img, axcam, spec, cam,
                                         r.width, r.height, r.background)

        return StepEntry(
            pipeline.distributed_plain_step_mxu(
                self.mesh, self.tf, spec, r, comp_cfg=c.composite,
                **self._decomp()),
            after=warp)

    def _plain_frame(self, key, entry):
        img, axcam = self._steps.run(key, entry, self._field_args())
        return entry.after(img, axcam, self.camera), None

    def frame_metadata(self, index: int):
        """VDIMetadata for the current camera/volume placement (≅ the
        per-frame VDIData the reference builds, DistributedVolumes.kt:
        706-716). NOTE: built from the CURRENT camera — call before the
        camera advances for exact correspondence."""
        from scenery_insitu_tpu.core.camera import (projection_matrix,
                                                    view_matrix)
        from scenery_insitu_tpu.core.vdi import VDIMetadata
        camera = self.camera
        r = self.cfg.render
        shape = (np.asarray(self.sim.field.shape)
                 if hasattr(self.sim, "field") else np.zeros(3, np.int32))
        return VDIMetadata.create(
            projection=projection_matrix(camera, r.width, r.height),
            view=view_matrix(camera),
            volume_dims=np.asarray(shape[::-1], np.float32),   # (x, y, z)
            window_dims=(r.width, r.height),
            nw=float(self._spacing[0]), index=index)

    def device_snapshot(self) -> dict:
        """Per-regime XLA cost-analysis snapshot (bytes/flops) of every
        compiled step this session holds, keyed like the step table
        (obs/device.cost_snapshot — the same numbers bench.py's roofline
        fields use). Best-effort: a step whose carried state is not
        seeded yet, or whose mode takes operands this generic path does
        not reconstruct, reports as unavailable rather than raising;
        lowering hits the compile cache, so this is cheap after the
        first frame. The snapshot is also recorded as an obs event so a
        metrics file carries the device-side truth next to the spans."""
        from scenery_insitu_tpu.obs import device as _dev

        table = self._steps
        snaps = {}
        if self.mode in ("vdi", "plain"):
            args = self._field_args()
            if table.fixed is not None:
                snaps["gather" if self.mode == "vdi" else "plain"] = \
                    _dev.cost_snapshot(table.fixed, *args)
            for key, entry in table.steps.items():
                carried = [store.get(key) for seed, store in
                           ((entry.seed_thr, table.thr),
                            (entry.seed_reuse, table.reuse))
                           if seed is not None]
                if any(state is None for state in carried):
                    snaps[str(key)] = {"source": "unavailable",
                                       "error": "carried state not "
                                                "seeded yet"}
                    continue
                snaps[str(key)] = _dev.cost_snapshot(entry.step, *args,
                                                     *carried)
        else:
            # hybrid/particle steps take mode-specific operands this
            # generic path does not reconstruct — report them as
            # unavailable rather than returning an empty dict
            for key in (list(table.steps) or [self.mode]):
                snaps[str(key)] = {"source": "unavailable",
                                   "error": f"mode {self.mode!r} operands "
                                            "not snapshotted"}
        if snaps:
            self.obs.event("device_snapshot", frame=self.frame_index,
                           regimes=list(snaps))
        return snaps


def vdi_sink(directory: str, dataset: str = "session", every: int = 1,
             codec: str = "zstd", workers: int = 1) -> Sink:
    """Dump composited VDIs as .npz artifacts — the render-product
    checkpoint stream offline renderers replay (≅ saveFinal VDIDataIO +
    buffer dumps, DistributedVolumes.kt:846-851, 910-915).

    ``workers`` threads io.vdi_io.save_vdi's per-member compression
    (byte-identical artifacts, shorter sink time — wire it to
    cfg.delivery.encode_workers on the async delivery plane)."""
    from scenery_insitu_tpu.core.vdi import VDI as _VDI
    from scenery_insitu_tpu.io.vdi_io import dump_path, save_vdi

    def sink(index: int, payload: dict) -> None:
        if index % every or "vdi_color" not in payload:
            return
        save_vdi(dump_path(directory, dataset, index, "vdi"),
                 _VDI(payload["vdi_color"], payload["vdi_depth"]),
                 codec=codec, workers=workers)

    return sink


def vdi_tile_sink(directory: str, dataset: str = "session", every: int = 1,
                  codec: str = "zstd", workers: int = 1) -> Sink:
    """Tile-granular twin of `vdi_sink` for ``InSituSession.tile_sinks``
    (composite.schedule == "waves"): each finished column-block tile is
    dumped as its own .npz the moment it is delivered — an offline
    consumer can start on the first columns before the frame closes. The
    artifact carries its (tile, tiles, col0) placement
    (io.vdi_io.save_vdi ``tile=``), so `io.vdi_io.load_vdi_tile` can
    reassemble frames."""
    from scenery_insitu_tpu.core.vdi import VDI as _VDI
    from scenery_insitu_tpu.io.vdi_io import dump_path, save_vdi

    def sink(index: int, payload: dict) -> None:
        if index % every or "vdi_color" not in payload \
                or "tile" not in payload:
            return
        save_vdi(dump_path(directory, dataset, index,
                           f"vditile{payload['tile']:02d}"),
                 _VDI(payload["vdi_color"], payload["vdi_depth"]),
                 payload.get("meta"), codec=codec,
                 tile=(payload["tile"], payload["tiles"],
                       payload["col0"]), workers=workers)

    return sink


def png_sink(directory: str, gamma: float = 2.2, every: int = 1) -> Sink:
    """Dump frames/VDI same-view decodes as PNGs (≅ the reference's
    screenshot + SystemHelpers.dumpToFile outputs)."""
    from scenery_insitu_tpu.core.vdi import render_vdi_same_view
    from scenery_insitu_tpu.utils.image import save_png
    os.makedirs(directory, exist_ok=True)

    def sink(index: int, payload: dict) -> None:
        if index % every:
            return
        if "image" in payload:
            img = payload["image"]
        else:
            img = np.asarray(render_vdi_same_view(
                VDI(jnp.asarray(payload["vdi_color"]),
                    jnp.asarray(payload["vdi_depth"]))))
        save_png(os.path.join(directory, f"frame{index:05d}.png"), img, gamma)

    return sink
