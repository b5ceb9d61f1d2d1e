"""Tile-wave pipelined frames (CompositeConfig.schedule="waves") vs the
monolithic frame schedule: lossless waves must be parity-exact (<=1e-5,
the PR-6 fusion-noise gate — separately compiled programs) across every
distributed step builder on the 8-device virtual mesh, the tile-granular
delivery path must emit column blocks in order before the frame closes,
and the traffic model must account the overlap (docs/PERF.md "Tile
waves")."""

import jax.numpy as jnp
import numpy as np
import pytest

from scenery_insitu_tpu.config import (CompositeConfig, RenderConfig,
                                       SliceMarchConfig, VDIConfig)
from scenery_insitu_tpu.core.camera import Camera
from scenery_insitu_tpu.core.transfer import TransferFunction
from scenery_insitu_tpu.core.volume import procedural_volume
from scenery_insitu_tpu.ops.composite import modeled_exchange_traffic
from scenery_insitu_tpu.parallel.mesh import make_mesh
from scenery_insitu_tpu.parallel.pipeline import (distributed_plain_step,
                                                  distributed_vdi_step,
                                                  shard_volume)

W = H = 16
STEPS = 48
N = 8
T = 2           # wave tiles per rank block in these tests
ATOL = 1e-5     # separately-compiled schedules carry ~1-ulp fusion noise


def _cam(eye=(0.0, 0.2, 4.0)):
    return Camera.create(eye, fov_y_deg=50.0, near=0.5, far=20.0)


def _tf():
    return TransferFunction.ramp(0.05, 0.8, 0.7)


def _mxu_spec(cam, vol, scale=2.0):
    from scenery_insitu_tpu.ops import slicer

    return slicer.make_spec(cam, vol.data.shape,
                            SliceMarchConfig(matmul_dtype="f32",
                                             scale=scale),
                            multiple_of=N)


def _assert_vdi_close(a, b, atol=ATOL):
    ac, ad = np.asarray(a[0]), np.asarray(a[1])
    bc, bd = np.asarray(b[0]), np.asarray(b[1])
    np.testing.assert_allclose(ac, bc, atol=atol, rtol=0)
    assert (np.isinf(ad) == np.isinf(bd)).all()
    fin = np.isfinite(ad)
    np.testing.assert_allclose(ad[fin], bd[fin], atol=atol, rtol=0)


# ------------------------------------------------- wave column helpers

def test_wave_cols_roundtrip():
    from scenery_insitu_tpu.ops import slicer

    x = jnp.arange(3 * 24, dtype=jnp.float32).reshape(3, 24)
    acc = jnp.zeros_like(x)
    for w in range(2):
        xw = slicer.wave_cols(x, 4, 2, jnp.int32(w))
        ref = np.asarray(x).reshape(3, 4, 2, 3)[:, :, w].reshape(3, 12)
        np.testing.assert_array_equal(np.asarray(xw), ref)
        acc = slicer.wave_update_cols(acc, xw, 4, 2, jnp.int32(w))
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(x))


def test_wave_block_validation():
    from scenery_insitu_tpu.ops import slicer

    assert slicer.wave_block(32, 8, 2) == 2
    with pytest.raises(ValueError, match="wave_tiles"):
        slicer.wave_block(16, 8, 3)


def test_wave_tiles_config_validation():
    with pytest.raises(ValueError, match="schedule"):
        CompositeConfig(schedule="tiles")
    with pytest.raises(ValueError, match="wave_tiles"):
        CompositeConfig(wave_tiles=0)


def test_wave_geometry_rejected_at_build():
    """A width that does not split into ranks * wave_tiles blocks fails
    when the step is BUILT, not deep inside a trace."""
    mesh = make_mesh(N)
    with pytest.raises(ValueError, match="wave_tiles"):
        distributed_vdi_step(
            mesh, _tf(), W, H,
            VDIConfig(max_supersegments=6, adaptive_iters=2),
            CompositeConfig(max_output_supersegments=8, schedule="waves",
                            wave_tiles=3), max_steps=STEPS)


# ------------------------------------------------ parity: every builder

def _run_vdi_step(schedule, vol, cam, exchange="all_to_all"):
    mesh = make_mesh(N)
    ccfg = CompositeConfig(max_output_supersegments=8, adaptive_iters=2,
                           exchange=exchange, schedule=schedule,
                           wave_tiles=T)
    step = distributed_vdi_step(
        mesh, _tf(), W, H, VDIConfig(max_supersegments=6,
                                     adaptive_iters=2),
        ccfg, max_steps=STEPS)
    vdi = step(shard_volume(vol.data, mesh), vol.origin, vol.spacing, cam)
    return vdi.color, vdi.depth


@pytest.mark.parametrize("exchange", ["all_to_all", "ring"])
def test_waves_vdi_step_matches_frame(exchange):
    """Gather-engine VDI chain: lossless waves == the frame schedule
    under BOTH per-wave exchange modes (the waves scan reuses the frame
    compositor per wave — bitwise on this path)."""
    vol = procedural_volume(16, kind="blobs")
    frame = _run_vdi_step("frame", vol, _cam(), exchange)
    waves = _run_vdi_step("waves", vol, _cam(), exchange)
    _assert_vdi_close(waves, frame)


@pytest.mark.parametrize("eye", [(0.0, 0.2, 4.0),    # march axis z
                                 (3.8, 0.3, 0.6)])   # march axis x
def test_waves_mxu_step_matches_frame(eye):
    """MXU slice-march chain in both march regimes: the tile-scoped wave
    march (u-sliced wave camera, shared permuted copy + pyramid) must
    reproduce the monolithic march + composite."""
    from scenery_insitu_tpu.parallel.pipeline import distributed_vdi_step_mxu

    mesh = make_mesh(N)
    vol = procedural_volume(16, kind="blobs")
    cam = _cam(eye)
    spec = _mxu_spec(cam, vol)
    data = shard_volume(vol.data, mesh)
    vcfg = VDIConfig(max_supersegments=6, adaptive_iters=2)
    outs = {}
    for sched in ("frame", "waves"):
        ccfg = CompositeConfig(max_output_supersegments=8,
                               adaptive_iters=2, schedule=sched,
                               wave_tiles=T)
        step = distributed_vdi_step_mxu(mesh, _tf(), spec, vcfg, ccfg)
        vdi, meta = step(data, vol.origin, vol.spacing, cam)
        outs[sched] = (vdi.color, vdi.depth, np.asarray(meta.window_dims))
    _assert_vdi_close(outs["waves"][:2], outs["frame"][:2])
    # the wave meta must describe the FULL frame, not one wave's columns
    np.testing.assert_array_equal(outs["waves"][2], outs["frame"][2])


def test_waves_mxu_temporal_threshold_carry_matches():
    """Temporal mode: each wave updates only its own threshold columns;
    across 3 carried frames both the per-frame composites and the final
    threshold maps must match the frame schedule."""
    from scenery_insitu_tpu.parallel.pipeline import (
        distributed_initial_threshold_mxu, distributed_vdi_step_mxu_temporal)

    mesh = make_mesh(N)
    vol = procedural_volume(16, kind="blobs")
    cam = _cam()
    cfg_t = VDIConfig(max_supersegments=6, adaptive_mode="temporal")
    spec = _mxu_spec(cam, vol)
    data = shard_volume(vol.data, mesh)
    runs = {}
    for sched in ("frame", "waves"):
        comp = CompositeConfig(max_output_supersegments=8,
                               adaptive_iters=2, schedule=sched,
                               wave_tiles=T)
        thr = distributed_initial_threshold_mxu(mesh, _tf(), spec, cfg_t)(
            data, vol.origin, vol.spacing, cam)
        step = distributed_vdi_step_mxu_temporal(mesh, _tf(), spec, cfg_t,
                                                 comp)
        frames = []
        for _ in range(3):
            (vdi, _), thr = step(data, vol.origin, vol.spacing, cam, thr)
            frames.append((np.asarray(vdi.color), np.asarray(vdi.depth)))
        runs[sched] = (frames, np.asarray(thr.thr))
    np.testing.assert_allclose(runs["waves"][1], runs["frame"][1],
                               atol=1e-6, rtol=0)
    for fr_w, fr_f in zip(runs["waves"][0], runs["frame"][0]):
        _assert_vdi_close(fr_w, fr_f)


def test_waves_plain_step_matches_frame():
    """Plain gather chain: the wave scan slices pre-rendered fragments,
    so frames must be bitwise identical."""
    mesh = make_mesh(N)
    vol = procedural_volume(16, kind="shell")
    cfg = RenderConfig(max_steps=STEPS, early_exit_alpha=1.1,
                       background=(1.0, 0.2, 0.1, 1.0))
    data = shard_volume(vol.data, mesh)
    imgs = {}
    for sched in ("frame", "waves"):
        step = distributed_plain_step(
            mesh, _tf(), W, H, cfg,
            comp_cfg=CompositeConfig(schedule=sched, wave_tiles=T))
        imgs[sched] = np.asarray(step(data, vol.origin, vol.spacing,
                                      _cam()))
    np.testing.assert_array_equal(imgs["waves"], imgs["frame"])


def test_waves_plain_mxu_step_matches_frame():
    """Plain MXU chain: tile-scoped render_slices per wave (shared
    permuted copy + occupancy gate) + per-wave exchange."""
    from scenery_insitu_tpu.parallel.pipeline import (
        distributed_plain_step_mxu)

    mesh = make_mesh(N)
    vol = procedural_volume(16, kind="blobs")
    cam = _cam()
    spec = _mxu_spec(cam, vol)
    data = shard_volume(vol.data, mesh)
    imgs = {}
    for sched in ("frame", "waves"):
        step = distributed_plain_step_mxu(
            mesh, _tf(), spec,
            comp_cfg=CompositeConfig(schedule=sched, wave_tiles=T))
        img, _ = step(data, vol.origin, vol.spacing, cam)
        imgs[sched] = np.asarray(img)
    np.testing.assert_allclose(imgs["waves"], imgs["frame"], atol=ATOL,
                               rtol=0)


def test_waves_hybrid_step_matches_frame():
    """Hybrid frame: the VDI half runs at wave granularity, the splat
    half inserts into the assembled block — whole frames must match."""
    import jax

    from scenery_insitu_tpu.parallel.pipeline import (
        distributed_hybrid_step_mxu)
    from scenery_insitu_tpu.parallel.particles import shard_particles

    mesh = make_mesh(N)
    vol = procedural_volume(16, kind="blobs")
    cam = _cam()
    spec = _mxu_spec(cam, vol)
    vcfg = VDIConfig(max_supersegments=6, adaptive_iters=2)
    pos = jax.random.uniform(jax.random.PRNGKey(7), (64, 3),
                             minval=-0.8, maxval=0.8)
    vel = jax.random.normal(jax.random.PRNGKey(8), (64, 3)) * 0.1
    data = shard_volume(vol.data, mesh)
    p = shard_particles(pos, mesh)
    v = shard_particles(vel, mesh)
    imgs = {}
    for sched in ("frame", "waves"):
        ccfg = CompositeConfig(max_output_supersegments=8,
                               adaptive_iters=2, schedule=sched,
                               wave_tiles=T)
        step = distributed_hybrid_step_mxu(mesh, _tf(), spec, vcfg, ccfg,
                                           radius=0.05, stamp=3)
        img, _ = step(data, vol.origin, vol.spacing, p, v, cam)
        imgs[sched] = np.asarray(img)
    np.testing.assert_allclose(imgs["waves"], imgs["frame"], atol=ATOL,
                               rtol=0)


# -------------------------------------------- degrade + observability

def test_waves_single_rank_degrades_to_frame():
    from scenery_insitu_tpu import obs

    mesh = make_mesh(1)
    vol = procedural_volume(8, kind="blobs")
    step = distributed_vdi_step(
        mesh, _tf(), 8, 8, VDIConfig(max_supersegments=4,
                                     adaptive_iters=2),
        CompositeConfig(max_output_supersegments=6, schedule="waves",
                        wave_tiles=2), max_steps=16)
    vdi = step(shard_volume(vol.data, mesh), vol.origin, vol.spacing,
               _cam())
    assert np.isfinite(np.asarray(vdi.color)).all()
    assert any(e["component"] == "composite.schedule"
               and e["from"] == "waves" and e["to"] == "frame"
               for e in obs.ledger())


def test_waves_build_emits_obs_counters():
    """The wave build mints schedule counters and one build event whose
    traffic block carries the overlap accounting
    (docs/OBSERVABILITY.md)."""
    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.parallel.pipeline import distributed_vdi_step_mxu

    rec = obs.Recorder(enabled=True)
    prev = obs.set_recorder(rec)
    try:
        mesh = make_mesh(N)
        vol = procedural_volume(16, kind="blobs")
        cam = _cam()
        spec = _mxu_spec(cam, vol)
        step = distributed_vdi_step_mxu(
            mesh, _tf(), spec, VDIConfig(max_supersegments=6,
                                         adaptive_iters=2),
            CompositeConfig(max_output_supersegments=8, adaptive_iters=2,
                            schedule="waves", wave_tiles=T))
        step(shard_volume(vol.data, mesh), vol.origin, vol.spacing, cam)
    finally:
        obs.set_recorder(prev)
    assert rec.counters.get("wave_schedule_builds", 0) >= 1
    assert rec.counters.get("wave_steps_built", 0) >= T
    builds = [e for e in rec.events
              if e.get("name") == "wave_schedule_build"]
    assert builds and builds[0]["attrs"]["march_per_wave"]
    t = builds[0]["attrs"]["traffic"]
    assert t["schedule"] == "waves" and t["wave_tiles"] == T
    assert t["ici_bytes_hidden_per_rank"] + t["ici_bytes_exposed_per_rank"] \
        == t["ici_bytes_per_rank"]


def test_modeled_traffic_overlap_accounting():
    """Waves change WHEN bytes move, not how many: hidden + exposed ==
    the frame schedule's total, hidden fraction = (T-1)/T, per-pixel
    merge working set unchanged."""
    frame = modeled_exchange_traffic(8, 16, 720, 1280, k_out=16)
    waves = modeled_exchange_traffic(8, 16, 720, 1280, k_out=16,
                                     schedule="waves", wave_tiles=4)
    assert frame["schedule"] == "frame" and "wave_tiles" not in frame
    assert waves["ici_bytes_per_rank"] == frame["ici_bytes_per_rank"]
    assert waves["ici_bytes_per_wave_per_rank"] * 4 \
        == waves["ici_bytes_per_rank"]
    assert (waves["ici_bytes_hidden_per_rank"]
            + waves["ici_bytes_exposed_per_rank"]
            == waves["ici_bytes_per_rank"])
    assert waves["overlap_hidden_frac"] == 0.75
    assert waves["peak_stream_slots_per_pixel"] \
        == frame["peak_stream_slots_per_pixel"]


# ---------------------------------------------- tile-granular delivery

def _waves_session(tmp_path, tile_sink=None, frames=2):
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.session import InSituSession

    cfg = FrameworkConfig().with_overrides(
        "render.width=32", "render.height=24", "render.max_steps=16",
        "vdi.max_supersegments=4", "vdi.adaptive_iters=2",
        "composite.max_output_supersegments=6",
        "composite.adaptive_iters=2",
        "composite.schedule=waves", "composite.wave_tiles=2",
        "sim.grid=[16,16,16]", "sim.steps_per_frame=1")
    sess = InSituSession(cfg)
    if tile_sink is not None:
        sess.tile_sinks.append(tile_sink)
    return sess


def test_partial_frame_tile_delivery_ordering(tmp_path):
    """Tiles arrive in ascending column order, cover the full width
    exactly once, and ALL precede the frame's own sinks (the partial
    frame is consumable before the frame closes)."""
    events = []

    def tile_sink(index, payload):
        assert payload["tiles"] == 8 * 2
        events.append(("tile", index, payload["tile"], payload["col0"],
                       payload["vdi_color"].shape[-1]))

    sess = _waves_session(tmp_path, tile_sink)
    sess.sinks.append(lambda i, p: events.append(("frame", i)))
    sess.run(2)
    frames = sorted({e[1] for e in events if e[0] == "tile"})
    assert frames == [0, 1]
    for f in frames:
        tiles = [e for e in events if e[0] == "tile" and e[1] == f]
        # ascending, exactly once, covering [0, 32)
        assert [t[2] for t in tiles] == list(range(16))
        assert [t[3] for t in tiles] == [i * 2 for i in range(16)]
        assert sum(t[4] for t in tiles) == 32
        # every tile of frame f lands before frame f's frame sink
        fi = events.index(("frame", f))
        assert all(events.index(t) < fi for t in tiles)
    assert sess.obs.counters.get("tiles_delivered", 0) == 2 * 16


def test_vdi_tile_sink_roundtrip(tmp_path):
    """Dumped tiles reassemble the frame (io.vdi_io tile placement)."""
    from scenery_insitu_tpu.io.vdi_io import load_vdi_tile
    from scenery_insitu_tpu.runtime.session import vdi_tile_sink

    d = str(tmp_path)
    frames = {}

    def capture(index, payload):
        frames.setdefault(index, []).append(payload)

    sess = _waves_session(tmp_path, vdi_tile_sink(d, codec="zlib"))
    sess.tile_sinks.append(capture)
    sess.run(1)
    tiles = frames[0]
    import glob
    import os

    paths = sorted(glob.glob(os.path.join(d, "*vditile*_00000.npz")))
    assert len(paths) == len(tiles) == 16
    cols = []
    for p in paths:
        vdi, meta, tile = load_vdi_tile(p)
        assert tile is not None and tile[1] == 16
        cols.append((tile[2], np.asarray(vdi.color)))
    cols.sort(key=lambda c: c[0])
    whole = np.concatenate([c[1] for c in cols], axis=-1)
    ref = np.concatenate([t["vdi_color"] for t in
                          sorted(tiles, key=lambda t: t["col0"])],
                         axis=-1)
    np.testing.assert_array_equal(whole, ref)


def test_gather_vdi_tiles_matches_compressed():
    """The rank-0 host gather's tile-granular path yields column blocks
    in order; concatenation == the whole-frame gather."""
    from scenery_insitu_tpu.parallel.multihost import (gather_vdi_compressed,
                                                       gather_vdi_tiles)

    mesh = make_mesh(N)
    vol = procedural_volume(16, kind="blobs")
    step = distributed_vdi_step(
        mesh, _tf(), W, H, VDIConfig(max_supersegments=4,
                                     adaptive_iters=2),
        CompositeConfig(max_output_supersegments=6, adaptive_iters=2),
        max_steps=24)
    vdi = step(shard_volume(vol.data, mesh), vol.origin, vol.spacing,
               _cam())
    color, depth = gather_vdi_compressed(vdi, codec="zlib")
    tiles = list(gather_vdi_tiles(vdi, codec="zlib"))
    assert [t[0] for t in tiles] == sorted(t[0] for t in tiles)
    np.testing.assert_array_equal(
        np.concatenate([t[1] for t in tiles], -1), color)
    np.testing.assert_array_equal(
        np.concatenate([t[2] for t in tiles], -1), depth)


def test_publish_tile_roundtrip():
    """VDIPublisher.publish_tile -> VDISubscriber.receive_tile carries
    the placement header; plain receive() still decodes the buffers."""
    pytest.importorskip("zmq")
    import time

    from scenery_insitu_tpu.core.vdi import VDI, VDIMetadata
    from scenery_insitu_tpu.runtime.streaming import (VDIPublisher,
                                                      VDISubscriber)

    pub = VDIPublisher(bind="tcp://*:0", codec="zlib")
    sub = VDISubscriber(connect=pub.endpoint)
    time.sleep(0.3)
    color = np.random.default_rng(3).random((4, 4, 6, 4)).astype(np.float32)
    depth = np.random.default_rng(4).random((4, 2, 6, 4)).astype(np.float32)
    meta = VDIMetadata.create(projection=np.eye(4, dtype=np.float32),
                              view=np.eye(4, dtype=np.float32),
                              volume_dims=np.ones(3, np.float32),
                              window_dims=(16, 6), nw=0.1, index=7)
    got = None
    for _ in range(10):
        pub.publish_tile(VDI(color, depth), meta, tile=2, tiles=4, col0=8)
        got = sub.receive_tile(timeout_ms=500)
        if got is not None:
            break
    pub.close()
    sub.close()
    assert got is not None, "no tile message received"
    vdi, meta2, tile = got
    assert tile == {"tile": 2, "tiles": 4, "col0": 8}
    np.testing.assert_array_equal(np.asarray(vdi.color), color)
    assert int(np.asarray(meta2.index)) == 7
