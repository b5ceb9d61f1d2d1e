"""Scene-driven session: the operator boundary for EXTERNAL multi-grid
simulations (≅ the reference's C++-driven entry points — updateData with
per-partner grid lists, addVolume/updateVolume/setVolumeDims,
DistributedVolumeRenderer.kt:136-160, DistributedVolumes.kt:142-250 —
driving a render loop the sim paces).

Unlike InSituSession (which advances a built-in sim and runs the
even-slab distributed pipeline), SceneSession renders whatever grids the
driver has pushed into its MultiGridScene — arbitrary counts, uneven
extents, ghost layers — through the whole-scene VDI path, and feeds the
same sinks/steering machinery. The driver calls ``update_data`` /
``update_grid`` between frames exactly like OpenFPM called the JNI
callbacks.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence

import jax
import numpy as np

from scenery_insitu_tpu import obs as _obs
from scenery_insitu_tpu.config import FrameworkConfig
from scenery_insitu_tpu.core.camera import Camera
from scenery_insitu_tpu.core.scene import MultiGridScene
from scenery_insitu_tpu.core.transfer import TransferFunction, for_dataset
from scenery_insitu_tpu.runtime.failsafe import SinkGuard
from scenery_insitu_tpu.runtime.steps import StepEntry, StepTable

Sink = Callable[[int, dict], None]


class SceneSession:
    def __init__(self, cfg: Optional[FrameworkConfig] = None,
                 camera: Optional[Camera] = None,
                 tf: Optional[TransferFunction] = None,
                 sinks: Sequence[Sink] = (), log=None):
        self.cfg = cfg or FrameworkConfig()
        self.log = log or (lambda s: None)
        self.scene = MultiGridScene()
        # same recorder-wraps-timers layering as InSituSession (spans
        # feed the PhaseStats either way; events only when obs enabled)
        self.obs = _obs.Recorder.from_config(
            self.cfg.obs, rank=jax.process_index(), log=self.log,
            window=self.cfg.runtime.stats_window)
        self.timers = self.obs.timers
        # always take over the process slot (see InSituSession.__init__)
        _obs.set_recorder(self.obs)
        # same live SLO engine as InSituSession — the driver paces the
        # loop, so frame_ms is observed per render_frame call
        from scenery_insitu_tpu.obs.slo import SLOEngine
        self.slo = SLOEngine(self.cfg.slo, recorder=self.obs)
        self.tf = tf or for_dataset(self.cfg.runtime.dataset)
        self.camera = camera or Camera.create(
            (0.0, 0.6, 3.0), fov_y_deg=50.0, near=0.3, far=20.0)
        self._host_pose = None      # set by steer_session
        self._steer_seq = 0         # camera messages applied (drain_steering)
        self.sinks: List[Sink] = list(sinks)
        # same per-callable failure isolation as InSituSession (sinks +
        # on_steer run behind the guard; see drain_steering)
        self._sink_guard = SinkGuard(self.cfg.fault.max_sink_failures,
                                     log=self.log)
        # same asynchronous delivery plane as InSituSession (docs/PERF.md
        # "Async delivery"): delivery.enabled runs the frame sinks on a
        # background worker; close() drains (SceneSession has no tile
        # path, so jobs carry no tile payloads)
        self._delivery = None
        if self.cfg.delivery.enabled:
            from scenery_insitu_tpu.runtime.delivery import (
                DeliveryExecutor)
            self._delivery = DeliveryExecutor(
                self.cfg.delivery, self._sink_guard, [], self.sinks,
                recorder=self.obs, slo=self.slo, log=self.log)
        self.frame_index = 0
        self.orbit_rate = 0.0
        self.steering = None
        self.on_steer: List[Callable[[dict], None]] = []
        from scenery_insitu_tpu.ops import slicer as _slicer
        self._slicer = _slicer
        self.engine = _slicer.resolve_engine(self.cfg.slicer.engine)
        # keyed (regime, grid-set signature, ...). Bounded: a drifting
        # scene mints a new extent key per movement, and an unbounded
        # table would retain every stale executable + [G, nj, ni]
        # threshold state for the life of the session. Insertion order ≈
        # recency here (a key is inserted once and then only hit), so
        # dropping the oldest entries is an adequate LRU.
        self._steps = StepTable(self.obs, max_entries=8)
        self._extent_cache = None  # (lo, hi, sp, rounded tuple) host copy
        self._temporal = (self.cfg.runtime.generate_vdis
                          and self.engine == "mxu"
                          and self.cfg.vdi.adaptive
                          and self.cfg.vdi.adaptive_mode == "temporal")
        # runtime TF updates: drop compiled steps (TF is baked in)
        self.on_steer.append(self._apply_tf_message)

    def _apply_tf_message(self, msg: dict) -> None:
        """'tf' steering: drop the compiled steps and their threshold
        state so the next frame compiles with the new transfer function.
        Shared protocol logic (parsing, malformed-payload containment)
        lives in session.apply_tf_steering."""
        from scenery_insitu_tpu.runtime.session import apply_tf_steering

        apply_tf_steering(self, msg, self._steps.reset)

    # ------------------------------------------------- operator boundary
    def update_data(self, partner: int, grids, origins, spacing,
                    ghost_lo=None, ghost_hi=None) -> None:
        """≅ updateData(partnerNo, numGrids, grids, origins, ...)."""
        self.scene.update_data(partner, grids, origins, spacing,
                               ghost_lo, ghost_hi)
        self._extent_cache = None

    def update_grid(self, partner: int, gid: int, data) -> None:
        """≅ updateVolume(id, buffer) — new timestep for one grid.

        Does NOT invalidate the extent cache: update_grid only replaces
        grid DATA (MultiGridScene keeps origin/spacing/ghosts), so the
        world extent cannot change — and the canonical driver loop calls
        this every timestep, where a host/device sync per dispatch would
        stall the async frame pipeline. Layout changes go through
        `update_data`, which does invalidate."""
        self.scene.update_grid(partner, gid, data)

    # -------------------------------------------------------------- frames
    def render_frame(self) -> dict:
        if self.scene.num_grids == 0:
            raise RuntimeError("no grids; call update_data first "
                               "(≅ the reference spinning on missing data, "
                               "DistributedVolumes.kt:151-153 — made loud)")
        from scenery_insitu_tpu.runtime.session import (
            advance_camera_and_index, drain_steering)

        import time as _time

        t_f = _time.perf_counter()
        drain_steering(self)
        with self.obs.span("dispatch", frame=self.frame_index,
                           engine=self.engine,
                           grids=self.scene.num_grids):
            entry, key = self._step()
            if self._temporal:
                self._steps.enter(key)
            out = self._steps.run(key, entry, self._frame_args())
        with self.obs.span("fetch", frame=self.frame_index):
            if self.cfg.runtime.generate_vdis:
                vdi, meta = out
                payload = {"vdi_color": np.asarray(vdi.color),
                           "vdi_depth": np.asarray(vdi.depth),
                           "meta": meta._replace(
                               index=np.int32(self.frame_index))}
            else:
                payload = {"image": np.asarray(out)}
            payload["frame"] = self.frame_index
        if self._delivery is not None:
            self._delivery.submit(self.frame_index, payload)
        else:
            with self.obs.span("sinks", frame=self.frame_index):
                self._sink_guard.run(self.sinks, self.frame_index,
                                     payload)
        advance_camera_and_index(self)
        self.timers.frame_done()
        self.slo.observe("frame_ms", (_time.perf_counter() - t_f) * 1e3,
                         frame=self.frame_index - 1)
        # the driver paces this loop (no run() bracket to flush at), so
        # write the obs sinks at every stats-window boundary — flush()
        # rewrites whole snapshots, so the files are always loadable
        if self.frame_index % self.timers.window == 0:
            self.obs.flush()
        return payload

    def close(self) -> None:
        """End-of-campaign teardown: drain the async delivery queue,
        flush the final partial timer window + totals and write the obs
        sinks."""
        if self._delivery is not None:
            self._delivery.drain()
        self.timers.dump_totals()
        self.obs.flush()

    def prewarm_regimes(self, regimes=None) -> dict:
        """Precompile the render step for each (axis, sign) camera regime
        against the CURRENT scene (same rationale as
        InSituSession.prewarm_regimes: a regime crossing mid-session
        otherwise stalls on a fresh jit). Call after `update_data` —
        a later grid-set signature change recompiles regardless (the
        table is keyed on both). Temporal threshold state and the
        reentry tracker are snapshotted and restored; the camera and
        frame index are untouched. Returns {(axis, sign): seconds}."""
        import time as _time

        if self.scene.num_grids == 0:
            raise RuntimeError("no grids; call update_data first")
        # only the MXU VDI path compiles per regime — gather/plain steps
        # have no regime dependence and would fill the bounded step cache
        # with byte-identical duplicates
        if self.engine != "mxu" or not self.cfg.runtime.generate_vdis:
            return {}
        from scenery_insitu_tpu.runtime.session import regime_camera

        if regimes is None:
            regimes = [(a, s) for a in (0, 1, 2) for s in (1, -1)]
        cam0 = self.camera
        table = self._steps
        kept = table.snapshot()
        times = {}
        try:
            for regime in regimes:
                self.camera = regime_camera(cam0, regime, self._slicer)
                t0 = _time.perf_counter()
                entry, key = self._step()
                jax.block_until_ready(
                    table.run(key, entry, self._frame_args()))
                times[tuple(regime)] = round(_time.perf_counter() - t0, 2)
        finally:
            self.camera = cam0
            table.restore(kept)
            # drop restored threshold entries whose step was evicted by
            # the table's bound (they would be orphaned forever), and
            # keep the ACTIVE regime's step most-recent so prewarming
            # many regimes can't evict the one the loop is about to use
            table.thr = {kk: v for kk, v in table.thr.items()
                         if kk in table.steps}
            try:
                _, active_key = self._step()
                if active_key in table.steps:
                    table.steps[active_key] = table.steps.pop(active_key)
            except Exception:
                pass
        return times

    def _frame_args(self):
        gs = self.scene.grids
        return (tuple(g.volume.data for g in gs),
                tuple(g.volume.origin for g in gs),
                tuple(g.volume.spacing for g in gs), self.camera)

    def _step(self):
        """(`StepEntry`, table key) for the current camera regime and the
        current grid-set SIGNATURE — one compilation per signature, like
        InSituSession._regime_frame. Data, origins, spacings and the camera
        are traced; shapes + ghosts are static, and so is the mxu
        intermediate-grid spec, whose dims derive from the scene's world
        extent — hence the signature also carries the rounded global
        bounds + spacing (a driver that repartitions, moves grids, or
        changes resolution triggers exactly one recompile; same-extent
        timestep updates reuse the cache)."""
        from scenery_insitu_tpu.runtime.session import camera_regime

        regime = camera_regime(self, "scene_step")
        gs = self.scene.grids
        sig = tuple((tuple(g.volume.data.shape), g.ghost_lo, g.ghost_hi)
                    for g in gs)
        mxu_vdi = (self.cfg.runtime.generate_vdis and self.engine == "mxu")
        # only the mxu spec bakes extent-derived statics; the gather/plain
        # steps trace origins+spacings, so extent in THEIR key would force
        # a recompile per scene movement for nothing. The extent is cached
        # host-side (invalidated by update_data/update_grid) so cache-hit
        # frames never sync device values on the dispatch path.
        extent = None
        lo = hi = sp = None
        if mxu_vdi:
            if self._extent_cache is None:
                lo, hi = self.scene.global_bounds()
                sp = gs[0].volume.spacing
                self._extent_cache = (
                    lo, hi, sp,
                    tuple(round(float(x), 5) for arr in (lo, hi, sp)
                          for x in np.asarray(arr)))
            lo, hi, sp, extent = self._extent_cache
        key = (regime, sig, extent, self.engine,
               self.cfg.runtime.generate_vdis)
        entry = self._steps.steps.get(key)
        if entry is None:
            entry = self._steps.compile(
                key, lambda: self._build_step(regime, mxu_vdi, lo, hi, sp),
                self.frame_index, "scene_step", regime)
        return entry, key

    def _build_step(self, regime, mxu_vdi: bool, lo, hi, sp):
        gs = self.scene.grids
        ghosts = [(g.ghost_lo, g.ghost_hi) for g in gs]
        r = self.cfg.render
        cfg = self.cfg
        tf = self.tf
        spec = None
        if mxu_vdi:
            dims = tuple(int(round(float(d)))
                         for d in np.asarray((hi - lo) / sp))   # (x, y, z)
            spec = self._slicer.make_spec(self.camera,
                                          (dims[2], dims[1], dims[0]),
                                          cfg.slicer, axis_sign=regime)

        def scene_of(datas, origins, spacings):
            sc = MultiGridScene()
            for i, (d, o, s) in enumerate(zip(datas, origins, spacings)):
                sc.set_grid(0, i, d, o, s, *ghosts[i])
            return sc

        if self._temporal:
            def fn_out(datas, origins, spacings, cam, thr):
                sc = scene_of(datas, origins, spacings)
                out, meta, thr2 = sc.generate_vdi_mxu_temporal(
                    tf, cam, spec, thr, cfg.vdi, cfg.composite)
                return (out, meta), thr2

            return StepEntry(jax.jit(fn_out), seed_thr=jax.jit(
                lambda datas, origins, spacings, cam:
                scene_of(datas, origins, spacings).initial_thresholds(
                    tf, cam, spec, cfg.vdi)))

        def fn(datas, origins, spacings, cam):
            sc = scene_of(datas, origins, spacings)
            if mxu_vdi:
                return sc.generate_vdi_mxu(tf, cam, spec, cfg.vdi,
                                           cfg.composite)
            if cfg.runtime.generate_vdis:
                return sc.generate_vdi(tf, cam, r.width, r.height,
                                       cfg.vdi, cfg.composite,
                                       max_steps=r.max_steps)
            return sc.render(tf, cam, r.width, r.height, r)

        return StepEntry(jax.jit(fn))
