import glob

import numpy as np

from scenery_insitu_tpu.config import FrameworkConfig
from scenery_insitu_tpu.parallel.mesh import make_mesh
from scenery_insitu_tpu.runtime.session import InSituSession, png_sink


def _cfg(**kw):
    cfg = FrameworkConfig().with_overrides(
        "render.width=32", "render.height=24", "render.max_steps=24",
        "vdi.max_supersegments=6", "vdi.adaptive_iters=2",
        "composite.max_output_supersegments=8", "composite.adaptive_iters=2",
        "sim.grid=[16,16,16]", "sim.steps_per_frame=2", "runtime.stats_window=2")
    return cfg.with_overrides(*[f"{k}={v}" for k, v in kw.items()])


def test_session_vdi_loop(tmp_path):
    lines = []
    sess = InSituSession(_cfg(), mesh=make_mesh(4),
                         sinks=[png_sink(str(tmp_path))], log=lines.append)
    payload = sess.run(3)
    assert payload["frame"] == 2
    assert payload["vdi_color"].shape == (8, 4, 24, 32)
    assert np.isfinite(payload["vdi_color"]).all()
    assert len(glob.glob(str(tmp_path / "frame*.png"))) == 3
    assert sess.timers.stats["sim"].n == 3
    assert any("window of 2" in l for l in lines)


def test_session_plain_mode(tmp_path):
    cfg = _cfg(**{"runtime.generate_vdis": "false"})
    sess = InSituSession(cfg, mesh=make_mesh(2))
    payload = sess.run(2)
    assert payload["image"].shape == (4, 24, 32)


def test_session_vortex():
    cfg = _cfg(**{"sim.kind": "vortex"})
    sess = InSituSession(cfg, mesh=make_mesh(2))
    payload = sess.run(1)
    assert "vdi_color" in payload


def test_session_orbit_changes_camera():
    sess = InSituSession(_cfg(), mesh=make_mesh(2))
    sess.orbit_rate = 0.3
    eye0 = np.asarray(sess.camera.eye)
    sess.run(2)
    assert not np.allclose(eye0, np.asarray(sess.camera.eye))


def test_session_mxu_engine(tmp_path):
    """Session with the MXU slice-march engine: VDI frames on the virtual
    camera grid, metadata from the pipeline, engine cache per march regime."""
    from scenery_insitu_tpu.config import FrameworkConfig

    cfg = FrameworkConfig().with_overrides(
        "slicer.engine=mxu", "slicer.scale=1.0",
        "sim.grid=[16,16,16]", "sim.steps_per_frame=2",
        "vdi.max_supersegments=6", "vdi.adaptive_iters=2",
        "composite.max_output_supersegments=8", "mesh.num_devices=4")
    s = InSituSession(cfg)
    payload = s.run(3)
    assert s.engine == "mxu"
    assert payload["frame"] == 2
    assert payload["vdi_color"].ndim == 4
    ni = payload["vdi_color"].shape[-1]
    assert ni % 4 == 0                      # divisible by mesh size
    assert np.isfinite(payload["vdi_color"]).all()
    assert int(payload["meta"].index) == 2
    assert len(s._steps.steps) == 1


def test_session_mxu_temporal(tmp_path):
    """Session with carried temporal threshold state on the distributed
    MXU pipeline: seeded on the first frame of a regime, threaded after."""
    from scenery_insitu_tpu.config import FrameworkConfig

    cfg = FrameworkConfig().with_overrides(
        "slicer.engine=mxu", "slicer.scale=1.0",
        "sim.grid=[16,16,16]", "sim.steps_per_frame=2",
        "vdi.max_supersegments=6", "vdi.adaptive_mode=temporal",
        "composite.max_output_supersegments=8", "mesh.num_devices=4")
    s = InSituSession(cfg)
    payload = s.run(3)
    assert np.isfinite(payload["vdi_color"]).all()
    assert len(s._steps.thr) == 1             # one regime seeded
    thr = next(iter(s._steps.thr.values()))
    assert np.isfinite(np.asarray(thr.thr)).all()


def test_session_prewarm_regimes():
    """prewarm_regimes precompiles per-regime steps without touching the
    loop's own state: camera, sim frame index and temporal thresholds all
    restored; a later run() finds its regime already cached."""
    from scenery_insitu_tpu.config import FrameworkConfig

    cfg = FrameworkConfig().with_overrides(
        "slicer.engine=mxu", "slicer.scale=1.0",
        "sim.grid=[16,16,16]", "sim.steps_per_frame=2",
        "vdi.max_supersegments=6", "vdi.adaptive_mode=temporal",
        "composite.max_output_supersegments=8", "mesh.num_devices=4")
    s = InSituSession(cfg)
    eye0 = np.asarray(s.camera.eye).copy()
    start_regime = s._slicer.choose_axis(s.camera)
    times = s.prewarm_regimes(regimes=[start_regime, (0, -1)])
    assert set(times) == {start_regime, (0, -1)}
    assert all(t >= 0 for t in times.values())
    assert len(s._steps.steps) == 2           # both regimes compiled
    assert s._steps.thr == {}                 # threshold state untouched
    assert s.frame_index == 0               # no frames consumed
    assert np.allclose(eye0, np.asarray(s.camera.eye))
    # the first real frames run in start_regime: must reuse the
    # prewarmed step, not compile a third entry
    payload = s.run(2)
    assert np.isfinite(payload["vdi_color"]).all()
    assert len(s._steps.steps) == 2           # nothing new compiled


def test_session_prewarm_noop_modes():
    """Engines/modes without per-regime jit return {} untouched."""
    sess = InSituSession(_cfg(), mesh=make_mesh(2))   # gather engine on CPU
    assert sess.prewarm_regimes() == {}


def test_session_particle_mode():
    cfg = _cfg(**{"sim.kind": "lennard_jones", "sim.num_particles": 64,
                  "sim.particle_radius": 0.3})
    sess = InSituSession(cfg, mesh=make_mesh(2))
    payload = sess.run(2)
    assert payload["image"].shape == (4, 24, 32)
    assert payload["depth"].shape == (24, 32)
    assert np.isfinite(payload["image"]).all()


def test_session_sho_mode():
    cfg = _cfg(**{"sim.kind": "sho", "sim.num_particles": 32,
                  "sim.particle_radius": 0.05})
    sess = InSituSession(cfg, mesh=make_mesh(2))
    payload = sess.run(2)
    assert payload["image"].shape == (4, 24, 32)


def test_session_hybrid_mode():
    cfg = _cfg(**{"sim.kind": "hybrid", "sim.num_particles": 64,
                  "sim.particle_radius": 0.8,
                  "slicer.engine": "mxu", "slicer.matmul_dtype": "f32"})
    sess = InSituSession(cfg, mesh=make_mesh(2))
    payload = sess.run(2)
    assert payload["image"].shape == (4, 24, 32)
    assert np.isfinite(payload["image"]).all()


def test_bad_env_override_raises(monkeypatch):
    monkeypatch.setenv("SITPU_RENDER_WIDHT", "512")     # typo'd key
    try:
        FrameworkConfig.load()
        raise AssertionError("typo'd SITPU_* key must raise")
    except ValueError as e:
        assert "WIDHT" in str(e)


def test_env_override_applies(monkeypatch):
    monkeypatch.setenv("SITPU_RENDER_WIDTH", "512")
    assert FrameworkConfig.load().render.width == 512


def test_session_profile_trace(tmp_path):
    cfg = _cfg()
    sess = InSituSession(cfg, mesh=make_mesh(2))
    out = sess.run(2, profile_dir=str(tmp_path / "trace"))
    assert out
    import glob as _glob
    assert _glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)


def test_session_soak_state_bounded():
    """60-frame soak with an orbiting camera crossing march regimes:
    caches stay bounded, threshold state tracks the live regimes only,
    output stays finite (guards against stateful leaks in the temporal /
    compiled-step caches over long runs)."""
    from scenery_insitu_tpu.config import FrameworkConfig

    cfg = FrameworkConfig().with_overrides(
        "slicer.engine=mxu", "slicer.scale=1.0",
        "sim.grid=[12,12,12]", "sim.steps_per_frame=1",
        "vdi.max_supersegments=4", "vdi.adaptive_mode=temporal",
        "composite.max_output_supersegments=4", "mesh.num_devices=2")
    s = InSituSession(cfg)
    s.orbit_rate = 0.12        # ~57 frames per revolution: crosses regimes
    payload = s.run(60)
    assert np.isfinite(payload["vdi_color"]).all()
    # 4 regimes visited at most around one orbit in a horizontal plane
    assert len(s._steps.steps) <= 4
    assert len(s._steps.thr) <= 4
    assert len(s._pending_meta) <= 2   # metadata snapshots are drained


def test_session_plain_mxu_mode():
    """Plain-image session on the slice-march engine: mode 'plain' no
    longer routes the MXU engine through the gather raycaster."""
    cfg = _cfg(**{"runtime.generate_vdis": "false",
                  "slicer.engine": "mxu", "slicer.matmul_dtype": "f32"})
    sess = InSituSession(cfg, mesh=make_mesh(2))
    assert sess.mode == "plain" and sess.engine == "mxu"
    assert sess._steps.fixed is None    # per-regime MXU steps, not gather
    payload = sess.run(2)
    assert payload["image"].shape == (4, 24, 32)
    assert np.isfinite(payload["image"]).all()


def test_session_hybrid_temporal_mode():
    """Hybrid session with temporal thresholds: accepted (round 2 rejected
    it), carries per-regime threshold state, 1 march/frame."""
    cfg = _cfg(**{"sim.kind": "hybrid", "sim.num_particles": 64,
                  "sim.particle_radius": 0.8,
                  "slicer.engine": "mxu", "slicer.matmul_dtype": "f32",
                  "vdi.adaptive_mode": "temporal"})
    sess = InSituSession(cfg, mesh=make_mesh(2))
    assert sess._temporal
    payload = sess.run(3)
    assert payload["image"].shape == (4, 24, 32)
    assert np.isfinite(payload["image"]).all()
    assert any(k[0] == "hybrid" for k in sess._steps.thr)


def test_session_pending_meta_bounded_headless():
    """run(fetch=False) must hold constant memory: the metadata snapshot
    dict is bounded even though nothing ever fetches/pops it."""
    sess = InSituSession(_cfg(), mesh=make_mesh(2))
    sess.run(6, fetch=False)
    assert len(sess._pending_meta) <= 2


def test_session_prewarm_covers_orbit_crossing():
    """The verdict-8 'done' criterion, compile-count form: an orbit that
    CROSSES march regimes mid-run must find every step prewarmed — zero
    new compilations after startup (on hardware that is the 10-24 s
    mid-orbit stall; on CPU the cache count is the compile-free proxy)."""
    from scenery_insitu_tpu.config import FrameworkConfig

    cfg = FrameworkConfig().with_overrides(
        "slicer.engine=mxu", "slicer.scale=1.0",
        "sim.grid=[16,16,16]", "sim.steps_per_frame=1",
        "vdi.max_supersegments=4", "vdi.adaptive_mode=temporal",
        "composite.max_output_supersegments=6", "mesh.num_devices=2")
    s = InSituSession(cfg, mesh=make_mesh(2))
    times = s.prewarm_regimes()
    assert len(times) == 6
    n_steps = len(s._steps.steps)
    assert n_steps == 6
    # ~0.6 rad/frame crosses at least one regime boundary within 6 frames
    s.orbit_rate = 0.6
    payload = s.run(6)
    assert np.isfinite(payload["vdi_color"]).all()
    # the premise must actually hold: temporal mode seeds one threshold
    # entry per VISITED regime, so >= 2 proves the orbit really crossed
    assert len(s._steps.thr) >= 2
    assert len(s._steps.steps) == n_steps     # nothing compiled mid-orbit
