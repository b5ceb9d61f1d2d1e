"""Occupancy-driven render rebalancing (CompositeConfig.rebalance ==
"occupancy"; docs/PERF.md "Render rebalancing"): slice_plan unit
behavior (conservation, min-depth clamp, quantum rounding, hysteresis
stability), the reslab_z band shuffle (even-plan == halo_exchange_z
row-for-row, uneven band contents + clamp + zero padding, halo-depth
validation naming the offending rank), and composite invariance — a
REBALANCED frame must equal the EVEN frame across the builder matrix on
the 8-device virtual mesh.

Parity gates, and why each is what it is:
- gather VDI step: BITWISE. The distributed gather steps ladder their
  samples against the GLOBAL box (ops/vdi_gen sample_min/max), so every
  sample position, value, and supersegment boundary is identical under
  any render plan.
- mxu steps (both march regimes, waves cross, temporal): 1e-5 — the
  PR-6 fusion-noise gate for separately-compiled programs. The slice
  ladder is global, so with power-of-two voxel spacing the diffs here
  measure 0.0; the gate absorbs non-exact spacings.
- The scene keeps content >= 2 slices away from every band boundary of
  BOTH decompositions and under the per-rank K budget: a supersegment
  that straddles a rank cut is split at the cut (per-rank generation),
  which changes the VDI's segment STRUCTURE (not its radiance) — an
  inherent property of sort-last VDI generation, not of rebalancing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from scenery_insitu_tpu.config import (CompositeConfig, RenderConfig,
                                       SliceMarchConfig, VDIConfig)
from scenery_insitu_tpu.core.camera import Camera
from scenery_insitu_tpu.core.transfer import TransferFunction
from scenery_insitu_tpu.ops import occupancy as occ
from scenery_insitu_tpu.parallel.mesh import (halo_exchange_z, make_mesh,
                                              reslab_z, validate_plan)
from scenery_insitu_tpu.parallel.pipeline import (distributed_plain_step,
                                                  distributed_vdi_step,
                                                  shard_volume)
from jax import shard_map

N = 8
D = 32
HW = 16
PLAN = (8, 4, 4, 4, 4, 2, 2, 4)      # bounds 8,12,16,20,24,26,28
ATOL = 1e-5                          # PR-6 fusion-noise gate


def _cam(eye=(0.0, 0.2, 4.0)):
    return Camera.create(eye, fov_y_deg=50.0, near=0.5, far=20.0)


def _tf():
    return TransferFunction.ramp(0.05, 0.8, 0.7)


def _scene():
    """Skewed scene (live work concentrated low-z), smooth constant-value
    blobs >= 2 slices clear of every boundary of the even split AND of
    PLAN, voxel spacing an exact power of two (2/32)."""
    data = np.zeros((D, HW, HW), np.float32)
    blobs = [(1, 3, 0.3), (5, 7, 0.5), (9, 11, 0.7), (13, 15, 0.4),
             (17, 19, 0.6), (21, 23, 0.8), (29, 31, 0.45)]
    for a, b, v in blobs:
        data[a:b] = v
    vox = 2.0 / D
    origin = jnp.asarray([-HW * vox / 2, -HW * vox / 2, -1.0], jnp.float32)
    spacing = jnp.full((3,), vox, jnp.float32)
    return jnp.asarray(data), origin, spacing


def _mxu_spec(cam, cfg_kw=None):
    from scenery_insitu_tpu.ops import slicer

    return slicer.make_spec(cam, (D, HW, HW),
                            SliceMarchConfig(matmul_dtype="f32", scale=2.0,
                                             **(cfg_kw or {})),
                            multiple_of=N)


def _assert_vdi_close(a, b, atol=ATOL):
    ac, ad = np.asarray(a[0]), np.asarray(a[1])
    bc, bd = np.asarray(b[0]), np.asarray(b[1])
    np.testing.assert_allclose(ac, bc, atol=atol, rtol=0)
    assert (np.isinf(ad) == np.isinf(bd)).all()
    fin = np.isfinite(ad)
    np.testing.assert_allclose(ad[fin], bd[fin], atol=atol, rtol=0)


# ------------------------------------------------------- slice_plan units

def test_slice_plan_conservation():
    rng = np.random.default_rng(0)
    for _ in range(20):
        prof = rng.random(16)
        n = int(rng.integers(2, 9))
        plan = occ.slice_plan(prof, 64, n, min_depth=2,
                              quantum=int(rng.integers(1, 5)))
        assert len(plan) == n
        assert sum(plan) == 64
        assert min(plan) >= 2


def test_slice_plan_equalizes_skew():
    """All the live work in the first quarter -> the even split's
    straggler factor collapses under the plan. Uncapped
    (max_depth=d) the equalization is near-perfect; the DEFAULT cap
    (2 * ceil(d/n)) trades some of it for a bounded padding tax
    (every rank scans max(plan) chunks) but must still reduce."""
    prof = np.zeros(32)
    prof[:8] = 1.0
    even = occ.even_plan(128, 8)
    s_even = occ.straggler_factor(prof, 128, even)
    assert s_even > 2.0
    free = occ.slice_plan(prof, 128, 8, min_depth=4, quantum=1,
                          max_depth=128)
    assert occ.straggler_factor(prof, 128, free) < s_even / 1.5
    capped = occ.slice_plan(prof, 128, 8, min_depth=4, quantum=1)
    assert max(capped) <= 2 * (128 // 8)
    assert occ.straggler_factor(prof, 128, capped) < s_even
    # dense region split across more ranks than the even split gives it
    assert sum(1 for b in np.cumsum(capped)[:-1] if b <= 32) >= 3


def test_slice_plan_min_depth_clamp():
    prof = np.zeros(16)
    prof[0] = 100.0                      # all work in slice band 0
    plan = occ.slice_plan(prof, 32, 8, min_depth=3, quantum=1)
    assert sum(plan) == 32
    # min_depth 3 is infeasible for 8 ranks over 32 slices; it clamps to
    # d // n and every band still keeps at least that
    assert min(plan) >= min(3, 32 // 8)


def test_slice_plan_quantum_rounding():
    rng = np.random.default_rng(3)
    prof = rng.random(16)
    plan = occ.slice_plan(prof, 64, 4, min_depth=4, quantum=4)
    bounds = np.cumsum(plan)
    assert all(b % 4 == 0 for b in bounds)


def test_slice_plan_hysteresis_stability():
    rng = np.random.default_rng(4)
    prof = rng.random(16)
    plan = occ.slice_plan(prof, 64, 4, min_depth=2, quantum=1)
    # a small perturbation of the profile keeps the PREVIOUS plan object
    prof2 = prof + rng.normal(0, 0.01, 16).clip(-0.05, 0.05)
    plan2 = occ.slice_plan(prof2, 64, 4, min_depth=2, quantum=1,
                           prev=plan, hysteresis=0.5)
    assert plan2 == plan
    # hysteresis off tracks the perturbation freely (may or may not
    # move); a LARGE shift must break through hysteresis
    prof3 = prof[::-1].copy()
    plan3 = occ.slice_plan(prof3, 64, 4, min_depth=2, quantum=1,
                           prev=plan, hysteresis=0.25)
    assert sum(plan3) == 64


def test_plan_work_and_straggler():
    prof = np.ones(8)
    even = occ.even_plan(32, 4)
    w = occ.plan_work(prof, 32, even)
    assert len(w) == 4 and abs(max(w) - min(w)) < 1e-9
    assert abs(occ.straggler_factor(prof, 32, even) - 1.0) < 1e-9


def test_z_live_profile():
    tf = _tf()
    field = jnp.zeros((16, 8, 8), jnp.float32)
    field = field.at[4:8].set(0.5)       # one live z quarter
    prof = np.asarray(occ.z_live_profile(field, tf, nzb=4))
    assert prof.shape == (4,)
    assert prof[1] > 0.9 and prof[0] < 0.1 and prof[2] < 0.1


# ---------------------------------------------------------- reslab_z

def _run_sharded(fn, data, mesh):
    f = jax.jit(shard_map(fn, mesh=mesh, in_specs=P("ranks", None, None),
                          out_specs=P("ranks", None, None),
                          check_vma=False))
    return np.asarray(f(shard_volume(data, mesh)))


def test_reslab_even_plan_matches_halo_exchange():
    mesh = make_mesh(N)
    data = jnp.asarray(
        np.random.default_rng(0).random((D, 8, 8)).astype(np.float32))
    even = occ.even_plan(D, N)
    a = _run_sharded(lambda x: reslab_z(x, even, "ranks"), data, mesh)
    b = _run_sharded(lambda x: halo_exchange_z(x, "ranks"), data, mesh)
    np.testing.assert_array_equal(a, b)


def test_reslab_uneven_bands_clamp_and_padding():
    mesh = make_mesh(N)
    raw = np.random.default_rng(1).random((D, 8, 8)).astype(np.float32)
    starts = np.concatenate([[0], np.cumsum(PLAN)])
    pmax = max(PLAN)
    out = _run_sharded(lambda x: reslab_z(x, PLAN, "ranks"),
                       jnp.asarray(raw), mesh)
    out = out.reshape(N, pmax + 2, 8, 8)
    for r in range(N):
        p, g0 = PLAN[r], starts[r]
        # band rows: global [g0-1, g0+p+1) with edge clamp
        ref = raw[np.clip(np.arange(g0 - 1, g0 + p + 1), 0, D - 1)]
        np.testing.assert_array_equal(out[r, :p + 2], ref)
        # rows past the band + halo are zero (the march masks them; the
        # occupancy pyramid admits zero for them)
        assert (out[r, p + 2:] == 0).all()


def test_reslab_halo_depth_validation_names_rank_and_knob():
    with pytest.raises(ValueError, match=r"rank 5.*rebalance_min_depth"):
        validate_plan((8, 4, 4, 4, 4, 2, 2, 4), 8, h=3)


def test_plan_without_occupancy_rebalance_rejected():
    mesh = make_mesh(N)
    with pytest.raises(ValueError, match="rebalance"):
        distributed_vdi_step(
            mesh, _tf(), HW, HW, VDIConfig(max_supersegments=4),
            CompositeConfig(max_output_supersegments=6), plan=PLAN)


def test_rebalance_config_validation():
    with pytest.raises(ValueError, match="rebalance"):
        CompositeConfig(rebalance="auto")
    with pytest.raises(ValueError, match="rebalance_period"):
        CompositeConfig(rebalance_period=0)
    with pytest.raises(ValueError, match="rebalance_quantum"):
        CompositeConfig(rebalance_quantum=0)


# -------------------------------------- parity: rebalanced == even split

def _vdi_cfgs(rebalance):
    return (VDIConfig(max_supersegments=10, adaptive_iters=2),
            CompositeConfig(max_output_supersegments=12, adaptive_iters=2,
                            rebalance=rebalance))


def test_rebalanced_gather_vdi_step_bitwise():
    """Gather engine: the global sample ladder makes every sample
    position/value identical under any plan — BITWISE equality."""
    data, origin, spacing = _scene()
    mesh = make_mesh(N)
    sdata = shard_volume(data, mesh)
    outs = {}
    for p in (None, PLAN):
        vc, cc = _vdi_cfgs("occupancy" if p else "even")
        step = distributed_vdi_step(mesh, _tf(), HW, HW, vc, cc,
                                    max_steps=48, plan=p)
        v = step(sdata, origin, spacing, _cam())
        outs[p is not None] = (np.asarray(v.color), np.asarray(v.depth))
    np.testing.assert_array_equal(outs[True][0], outs[False][0])
    np.testing.assert_array_equal(outs[True][1], outs[False][1])


@pytest.mark.parametrize("eye", [(0.0, 0.2, 4.0),    # march axis z
                                 (3.8, 0.3, 0.6)])   # march axis x
def test_rebalanced_mxu_step_matches_even(eye):
    """MXU engine in both march regimes: the planned band march (z
    regime: w_bounds-masked padded band; x regime: v_bounds over the
    band interval) equals the even split at the 1e-5 gate."""
    from scenery_insitu_tpu.parallel.pipeline import distributed_vdi_step_mxu

    data, origin, spacing = _scene()
    mesh = make_mesh(N)
    sdata = shard_volume(data, mesh)
    cam = _cam(eye)
    spec = _mxu_spec(cam)
    outs = {}
    for p in (None, PLAN):
        vc, cc = _vdi_cfgs("occupancy" if p else "even")
        step = distributed_vdi_step_mxu(mesh, _tf(), spec, vc, cc, plan=p)
        v, meta = step(sdata, origin, spacing, cam)
        outs[p is not None] = (v.color, v.depth,
                               np.asarray(meta.volume_dims))
    _assert_vdi_close(outs[True][:2], outs[False][:2])
    # the metadata must keep describing the GLOBAL volume
    np.testing.assert_array_equal(outs[True][2], outs[False][2])


def test_rebalanced_waves_cross_matches_even_frame():
    """Waves x rebalance cross: a PLANNED band marched in tile waves
    still equals the even frame schedule."""
    from scenery_insitu_tpu.parallel.pipeline import distributed_vdi_step_mxu

    data, origin, spacing = _scene()
    mesh = make_mesh(N)
    sdata = shard_volume(data, mesh)
    cam = _cam()
    spec = _mxu_spec(cam)
    vc, cc = _vdi_cfgs("even")
    even, _ = distributed_vdi_step_mxu(mesh, _tf(), spec, vc, cc)(
        sdata, origin, spacing, cam)
    vc, cc = _vdi_cfgs("occupancy")
    cc = CompositeConfig(max_output_supersegments=12, adaptive_iters=2,
                         rebalance="occupancy", schedule="waves",
                         wave_tiles=2)
    waved, _ = distributed_vdi_step_mxu(mesh, _tf(), spec, vc, cc,
                                        plan=PLAN)(
        sdata, origin, spacing, cam)
    _assert_vdi_close((waved.color, waved.depth), (even.color, even.depth))


def test_rebalanced_mxu_temporal_matches_even():
    """Temporal mode: the planned seeding march + 3 carried frames match
    the even split (threshold maps included)."""
    from scenery_insitu_tpu.parallel.pipeline import (
        distributed_initial_threshold_mxu, distributed_vdi_step_mxu_temporal)

    data, origin, spacing = _scene()
    mesh = make_mesh(N)
    sdata = shard_volume(data, mesh)
    cam = _cam()
    spec = _mxu_spec(cam)
    cfg_t = VDIConfig(max_supersegments=10, adaptive_mode="temporal")
    runs = {}
    for p in (None, PLAN):
        cc = CompositeConfig(max_output_supersegments=12, adaptive_iters=2,
                             rebalance="occupancy" if p else "even")
        thr = distributed_initial_threshold_mxu(
            mesh, _tf(), spec, cfg_t, plan=p)(sdata, origin, spacing, cam)
        step = distributed_vdi_step_mxu_temporal(mesh, _tf(), spec, cfg_t,
                                                 cc, plan=p)
        frames = []
        for _ in range(3):
            (v, _), thr = step(sdata, origin, spacing, cam, thr)
            frames.append((np.asarray(v.color), np.asarray(v.depth)))
        runs[p is not None] = (frames, np.asarray(thr.thr))
    np.testing.assert_allclose(runs[True][1], runs[False][1], atol=1e-6,
                               rtol=0)
    for fr_p, fr_e in zip(runs[True][0], runs[False][0]):
        _assert_vdi_close(fr_p, fr_e)


def test_rebalanced_plain_steps_match_even():
    """Plain chains, both engines. Gather: global sample ladder (the
    one residual is the early-exit gate flipping within ~1 ulp of the
    threshold — bounded by one sample's alpha; gate 1e-5 holds on this
    scene). MXU: slice ladder exact."""
    from scenery_insitu_tpu.parallel.pipeline import (
        distributed_plain_step_mxu)

    data, origin, spacing = _scene()
    mesh = make_mesh(N)
    sdata = shard_volume(data, mesh)
    cam = _cam()
    for build in ("gather", "mxu"):
        imgs = {}
        for p in (None, PLAN):
            kw = dict(comp_cfg=CompositeConfig(
                rebalance="occupancy" if p else "even"), plan=p)
            if build == "gather":
                step = distributed_plain_step(
                    mesh, _tf(), HW, HW, RenderConfig(max_steps=48), **kw)
                out = step(sdata, origin, spacing, cam)
            else:
                step = distributed_plain_step_mxu(mesh, _tf(),
                                                  _mxu_spec(cam), **kw)
                out, _ = step(sdata, origin, spacing, cam)
            imgs[p is not None] = np.asarray(out)
        np.testing.assert_allclose(imgs[True], imgs[False], atol=ATOL,
                                   rtol=0, err_msg=build)


def test_rebalanced_hybrid_step_matches_even():
    from scenery_insitu_tpu.parallel.pipeline import (
        distributed_hybrid_step_mxu)
    from scenery_insitu_tpu.parallel.particles import shard_particles

    data, origin, spacing = _scene()
    mesh = make_mesh(N)
    sdata = shard_volume(data, mesh)
    cam = _cam()
    spec = _mxu_spec(cam)
    pos = jax.random.uniform(jax.random.PRNGKey(7), (64, 3),
                             minval=-0.8, maxval=0.8)
    vel = jax.random.normal(jax.random.PRNGKey(8), (64, 3)) * 0.1
    p_, v_ = shard_particles(pos, mesh), shard_particles(vel, mesh)
    imgs = {}
    for p in (None, PLAN):
        vc, cc = _vdi_cfgs("occupancy" if p else "even")
        step = distributed_hybrid_step_mxu(mesh, _tf(), spec, vc, cc,
                                           radius=0.05, stamp=3, plan=p)
        img, _ = step(sdata, origin, spacing, p_, v_, cam)
        imgs[p is not None] = np.asarray(img)
    np.testing.assert_allclose(imgs[True], imgs[False], atol=ATOL, rtol=0)


# --------------------------------------------- observability + session

def test_rebalance_build_emits_obs_counters():
    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.parallel.pipeline import distributed_vdi_step_mxu

    data, origin, spacing = _scene()
    rec = obs.Recorder(enabled=True)
    prev = obs.set_recorder(rec)
    try:
        mesh = make_mesh(N)
        vc, cc = _vdi_cfgs("occupancy")
        step = distributed_vdi_step_mxu(mesh, _tf(), _mxu_spec(_cam()),
                                        vc, cc, plan=PLAN)
        step(shard_volume(data, mesh), origin, spacing, _cam())
    finally:
        obs.set_recorder(prev)
    assert rec.counters.get("rebalance_steps_built", 0) >= 1
    builds = [e for e in rec.events if e.get("name") == "rebalance_build"]
    assert builds and builds[0]["attrs"]["plan"] == list(PLAN)
    assert builds[0]["attrs"]["max_depth"] == max(PLAN)


class _SkewedSim:
    """Static skewed field (content low-z only) for session replans."""

    kind = "static_skew"

    def __init__(self, d=16, hw=16):
        f = np.zeros((d, hw, hw), np.float32)
        f[1:4] = 0.6
        self.field = jnp.asarray(f)

    def advance(self, n):
        pass


def test_session_replans_and_rebuilds():
    """InSituSession under rebalance=occupancy: the host-side re-plan
    fetches live fractions, adopts an uneven plan, mints the
    rebalance_plan event + occupancy.replan ledger row, and the
    rebuilt steps keep rendering finite frames."""
    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.session import InSituSession

    cfg = FrameworkConfig().with_overrides(
        "render.width=16", "render.height=16", "render.max_steps=16",
        "vdi.max_supersegments=4", "vdi.adaptive_iters=2",
        "composite.max_output_supersegments=6", "composite.adaptive_iters=2",
        "composite.rebalance=occupancy", "composite.rebalance_period=1",
        "composite.rebalance_quantum=1", "composite.rebalance_min_depth=1",
        "composite.rebalance_hysteresis=0.05",
        "sim.grid=[16,16,16]", "sim.steps_per_frame=1",
        "obs.enabled=true")
    obs.clear_ledger()
    sess = InSituSession(cfg, sim=_SkewedSim())
    payload = sess.run(3)
    assert np.isfinite(payload["vdi_color"]).all()
    assert sess._plan is not None and sum(sess._plan) == 16
    assert sess._plan != occ.even_plan(16, N)
    assert sess.obs.counters.get("rebalance_replans", 0) >= 1
    ev = [e for e in sess.obs.events if e.get("name") == "rebalance_plan"]
    assert ev and ev[0]["attrs"]["plan"] == list(sess._plan)
    assert ev[0]["attrs"]["straggler_planned"] \
        <= ev[0]["attrs"]["straggler_even"]
    assert any(e["component"] == "occupancy.replan" for e in obs.ledger())


def test_session_rebalance_inert_on_single_rank():
    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.session import InSituSession

    cfg = FrameworkConfig().with_overrides(
        "render.width=16", "render.height=16", "render.max_steps=16",
        "vdi.max_supersegments=4", "vdi.adaptive_iters=2",
        "composite.max_output_supersegments=6", "composite.adaptive_iters=2",
        "composite.rebalance=occupancy",
        "sim.grid=[16,16,16]", "sim.steps_per_frame=1")
    obs.clear_ledger()
    sess = InSituSession(cfg, mesh=make_mesh(1), sim=_SkewedSim())
    sess.run(1)
    assert sess._plan is None
    assert any(e["component"] == "occupancy.rebalance"
               for e in obs.ledger())
