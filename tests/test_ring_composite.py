"""Ring-pipelined sort-last compositing (CompositeConfig.exchange="ring")
vs the monolithic all_to_all path: exact-parity checks on the 8-device
virtual mesh across the plain, VDI, temporal and hybrid steps, plus unit
tests of the pairwise ordered merge (ops.composite.merge_vdis_pairwise).
docs/PERF.md "Exchange modes" documents the memory model the capped test
exercises."""

import jax.numpy as jnp
import numpy as np
import pytest

from scenery_insitu_tpu.config import (CompositeConfig, RenderConfig,
                                       SliceMarchConfig, VDIConfig)
from scenery_insitu_tpu.core.camera import Camera
from scenery_insitu_tpu.core.transfer import TransferFunction
from scenery_insitu_tpu.core.volume import procedural_volume
from scenery_insitu_tpu.ops.composite import (merge_vdis_pairwise,
                                              modeled_exchange_traffic)
from scenery_insitu_tpu.parallel.mesh import make_mesh
from scenery_insitu_tpu.parallel.pipeline import (distributed_plain_step,
                                                  distributed_vdi_step,
                                                  shard_volume)

W = H = 16
STEPS = 48
N = 8


def _cam(eye=(0.0, 0.2, 4.0)):
    return Camera.create(eye, fov_y_deg=50.0, near=0.5, far=20.0)


def _tf():
    return TransferFunction.ramp(0.05, 0.8, 0.7)


def _stream(rng, k, h, w, live, lo=1.0, hi=5.0):
    """Random per-pixel depth-sorted segment stream with ``live`` live
    slots (empties masked: zero color, +inf depth)."""
    s = np.sort(rng.uniform(lo, hi, (k, h, w)), axis=0).astype(np.float32)
    e = (s + rng.uniform(0.01, 0.2, (k, h, w))).astype(np.float32)
    c = rng.uniform(0.0, 1.0, (k, 4, h, w)).astype(np.float32)
    mask = np.arange(k)[:, None, None] < live
    s = np.where(mask, s, np.inf)
    e = np.where(mask, e, np.inf)
    c = np.where(mask[:, None], c, 0.0)
    return jnp.asarray(c), jnp.asarray(np.stack([s, e], axis=1))


def _assert_vdi_equal(a, b, atol=0.0):
    """Color/depth equality that treats +inf empty slots as equal."""
    ac, ad = np.asarray(a[0]), np.asarray(a[1])
    bc, bd = np.asarray(b[0]), np.asarray(b[1])
    np.testing.assert_allclose(ac, bc, atol=atol, rtol=0)
    assert (np.isinf(ad) == np.isinf(bd)).all()
    fin = np.isfinite(ad)
    np.testing.assert_allclose(ad[fin], bd[fin], atol=atol, rtol=0)


# ------------------------------------------------ merge_vdis_pairwise units

def test_merge_pairwise_disjoint():
    """Depth-disjoint lists (the sort-last invariant): B entirely behind A
    → merged = concatenation, payloads moved bit-exactly."""
    rng = np.random.default_rng(1)
    ca, da = _stream(rng, 3, 2, 2, live=3, lo=1.0, hi=2.0)
    cb, db = _stream(rng, 3, 2, 2, live=3, lo=3.0, hi=4.0)
    mc, md = merge_vdis_pairwise(ca, da, cb, db)
    np.testing.assert_array_equal(np.asarray(mc),
                                  np.concatenate([ca, cb], axis=0))
    np.testing.assert_array_equal(np.asarray(md),
                                  np.concatenate([da, db], axis=0))


def test_merge_pairwise_overlapping():
    """Interleaved depth ranges merge into the globally sorted stream
    (matching a reference sort of the concatenation)."""
    rng = np.random.default_rng(2)
    ca, da = _stream(rng, 5, 3, 4, live=5)
    cb, db = _stream(rng, 4, 3, 4, live=4)
    mc, md = merge_vdis_pairwise(ca, da, cb, db)
    alls = np.concatenate([np.asarray(da)[:, 0], np.asarray(db)[:, 0]], 0)
    order = np.argsort(alls, axis=0, kind="stable")
    allc = np.concatenate([np.asarray(ca), np.asarray(cb)], axis=0)
    ref_c = np.take_along_axis(allc, order[:, None], axis=0)
    np.testing.assert_array_equal(np.asarray(mc), ref_c)
    np.testing.assert_array_equal(np.asarray(md)[:, 0],
                                  np.sort(alls, axis=0))


def test_merge_pairwise_empty_slots():
    """Empty (+inf) slots from both lists collect at the back with zero
    color; live counts add."""
    rng = np.random.default_rng(3)
    ca, da = _stream(rng, 4, 2, 3, live=2)
    cb, db = _stream(rng, 4, 2, 3, live=1)
    mc, md = merge_vdis_pairwise(ca, da, cb, db)
    mc, md = np.asarray(mc), np.asarray(md)
    assert np.isfinite(md[:3, 0]).all()          # 2 + 1 live slots first
    assert np.isinf(md[3:]).all()                # empties at the back
    assert (mc[3:] == 0.0).all()                 # with masked colors
    # one fully-empty pair stays fully empty
    ce, de = _stream(rng, 3, 2, 2, live=0)
    mc2, md2 = merge_vdis_pairwise(ce, de, ce, de)
    assert np.isinf(np.asarray(md2)).all()
    assert (np.asarray(mc2) == 0.0).all()


def test_merge_pairwise_truncation():
    """k_cap keeps the NEAREST segments and drops the farthest — the
    bounded-memory ring mode's contract."""
    rng = np.random.default_rng(4)
    ca, da = _stream(rng, 4, 2, 2, live=4)
    cb, db = _stream(rng, 4, 2, 2, live=4)
    full_c, full_d = merge_vdis_pairwise(ca, da, cb, db)
    cap_c, cap_d = merge_vdis_pairwise(ca, da, cb, db, k_cap=5)
    assert cap_c.shape[0] == 5 and cap_d.shape[0] == 5
    np.testing.assert_array_equal(np.asarray(cap_c), np.asarray(full_c)[:5])
    np.testing.assert_array_equal(np.asarray(cap_d), np.asarray(full_d)[:5])
    # a cap at or above Ka+Kb is a no-op
    same_c, same_d = merge_vdis_pairwise(ca, da, cb, db, k_cap=8)
    np.testing.assert_array_equal(np.asarray(same_c), np.asarray(full_c))
    np.testing.assert_array_equal(np.asarray(same_d), np.asarray(full_d))


def test_merge_pairwise_tie_prefers_accumulator():
    """Exactly-equal start depths order the accumulator (A) first."""
    da = jnp.asarray([[[[2.0]], [[2.5]]]])        # [1, 2, 1, 1]
    db = jnp.asarray([[[[2.0]], [[2.6]]]])
    ca = jnp.full((1, 4, 1, 1), 0.25, jnp.float32)
    cb = jnp.full((1, 4, 1, 1), 0.75, jnp.float32)
    mc, md = merge_vdis_pairwise(ca, da, cb, db)
    assert float(mc[0, 0, 0, 0]) == 0.25 and float(mc[1, 0, 0, 0]) == 0.75
    assert float(md[0, 1, 0, 0]) == 2.5
    assert float(md[1, 1, 0, 0]) == float(np.float32(2.6))


# -------------------------------------------- ring vs all_to_all step parity

def _vdi_steps_both(vcfg, ccfg_kw, vol, cam):
    mesh = make_mesh(N)
    data = shard_volume(vol.data, mesh)
    outs = {}
    for ex in ("all_to_all", "ring"):
        ccfg = CompositeConfig(exchange=ex, **ccfg_kw)
        step = distributed_vdi_step(mesh, _tf(), W, H, vcfg, ccfg,
                                    max_steps=STEPS)
        vdi = step(data, vol.origin, vol.spacing, cam)
        outs[ex] = (vdi.color, vdi.depth)
    return outs


def test_ring_vdi_step_matches_all_to_all():
    """8-rank gather-engine VDI chain: the ring composite must reproduce
    the all_to_all composite exactly (acceptance: bitwise or atol<=1e-6)."""
    vol = procedural_volume(16, kind="blobs")
    outs = _vdi_steps_both(
        VDIConfig(max_supersegments=6, adaptive_iters=2),
        dict(max_output_supersegments=8, adaptive_iters=2),
        vol, _cam())
    _assert_vdi_equal(outs["ring"], outs["all_to_all"], atol=1e-6)


def test_ring_vdi_step_nonadaptive_matches():
    """Fixed-threshold re-segmentation (no adaptive search) parity."""
    vol = procedural_volume(16, kind="shell")
    outs = _vdi_steps_both(
        VDIConfig(max_supersegments=5, adaptive=False, threshold=0.1),
        dict(max_output_supersegments=6, adaptive=False),
        vol, _cam())
    _assert_vdi_equal(outs["ring"], outs["all_to_all"], atol=1e-6)


def test_ring_capped_renders_close():
    """ring_slots=2K (the bounded-memory mode) is approximate on overfull
    pixels but must stay a faithful image of the lossless composite."""
    from scenery_insitu_tpu.core.vdi import VDI, render_vdi_same_view
    from scenery_insitu_tpu.utils.image import psnr

    vol = procedural_volume(16, kind="blobs")
    mesh = make_mesh(N)
    data = shard_volume(vol.data, mesh)
    vcfg = VDIConfig(max_supersegments=6, adaptive_iters=2)
    imgs = {}
    for slots in (0, 12):
        ccfg = CompositeConfig(max_output_supersegments=8, adaptive_iters=2,
                               exchange="ring", ring_slots=slots)
        step = distributed_vdi_step(mesh, _tf(), W, H, vcfg, ccfg,
                                    max_steps=STEPS)
        vdi = step(data, vol.origin, vol.spacing, _cam())
        imgs[slots] = np.asarray(render_vdi_same_view(
            VDI(vdi.color, vdi.depth)))
    assert np.isfinite(imgs[12]).all()
    q = psnr(imgs[0], imgs[12])
    assert q > 30.0, f"capped-ring PSNR {q:.1f} dB"


def test_ring_slots_below_k_rejected():
    vol = procedural_volume(16, kind="blobs")
    mesh = make_mesh(N)
    step = distributed_vdi_step(
        mesh, _tf(), W, H, VDIConfig(max_supersegments=6, adaptive_iters=2),
        CompositeConfig(max_output_supersegments=8, exchange="ring",
                        ring_slots=3), max_steps=STEPS)
    with pytest.raises(ValueError, match="ring_slots"):
        step(shard_volume(vol.data, mesh), vol.origin, vol.spacing, _cam())


def test_exchange_config_validation():
    with pytest.raises(ValueError, match="exchange"):
        CompositeConfig(exchange="butterfly")
    with pytest.raises(ValueError, match="ring_slots"):
        CompositeConfig(ring_slots=-1)


@pytest.mark.parametrize("eye", [(0.0, 0.2, 4.0),    # march axis z (sharded)
                                 (3.8, 0.3, 0.6)])   # march axis x (in-plane)
def test_ring_mxu_step_matches_all_to_all(eye):
    """MXU slice-march VDI chain in both march regimes: ring parity."""
    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.parallel.pipeline import distributed_vdi_step_mxu

    mesh = make_mesh(N)
    vol = procedural_volume(16, kind="blobs")
    cam = _cam(eye)
    vcfg = VDIConfig(max_supersegments=6, adaptive_iters=2)
    spec = slicer.make_spec(cam, vol.data.shape,
                            SliceMarchConfig(matmul_dtype="f32", scale=1.5),
                            multiple_of=N)
    data = shard_volume(vol.data, mesh)
    outs = {}
    for ex in ("all_to_all", "ring"):
        ccfg = CompositeConfig(max_output_supersegments=8, adaptive_iters=2,
                               exchange=ex)
        step = distributed_vdi_step_mxu(mesh, _tf(), spec, vcfg, ccfg)
        vdi, _ = step(data, vol.origin, vol.spacing, cam)
        outs[ex] = (vdi.color, vdi.depth)
    _assert_vdi_equal(outs["ring"], outs["all_to_all"], atol=1e-6)


def test_ring_mxu_temporal_threshold_carry_matches():
    """Temporal mode under ring exchange: the carried per-rank threshold
    state must evolve identically to the all_to_all run (generation is
    upstream of the exchange) and every frame's composite must match —
    the threshold-carry-across-ring-steps check."""
    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.parallel.pipeline import (
        distributed_initial_threshold_mxu, distributed_vdi_step_mxu_temporal)

    mesh = make_mesh(N)
    vol = procedural_volume(16, kind="blobs")
    cam = _cam()
    cfg_t = VDIConfig(max_supersegments=6, adaptive_mode="temporal")
    spec = slicer.make_spec(cam, vol.data.shape,
                            SliceMarchConfig(matmul_dtype="f32", scale=1.5),
                            multiple_of=N)
    data = shard_volume(vol.data, mesh)
    runs = {}
    for ex in ("all_to_all", "ring"):
        comp = CompositeConfig(max_output_supersegments=8, adaptive_iters=2,
                               exchange=ex)
        thr = distributed_initial_threshold_mxu(mesh, _tf(), spec, cfg_t)(
            data, vol.origin, vol.spacing, cam)
        step = distributed_vdi_step_mxu_temporal(mesh, _tf(), spec, cfg_t,
                                                 comp)
        frames = []
        for _ in range(3):
            (vdi, _), thr = step(data, vol.origin, vol.spacing, cam, thr)
            frames.append((np.asarray(vdi.color), np.asarray(vdi.depth)))
        runs[ex] = (frames, np.asarray(thr.thr))
    np.testing.assert_allclose(runs["ring"][1], runs["all_to_all"][1],
                               atol=1e-6, rtol=0)
    for fr_r, fr_a in zip(runs["ring"][0], runs["all_to_all"][0]):
        _assert_vdi_equal(fr_r, fr_a, atol=1e-6)


@pytest.mark.parametrize("background", [(0.0, 0.0, 0.0, 0.0),
                                        (1.0, 0.2, 0.1, 1.0)])
def test_ring_plain_step_matches_all_to_all(background):
    """Plain gather-path exchange: ring is restacked to source-rank order
    before the nearest-first composite → bitwise-identical frames."""
    mesh = make_mesh(N)
    vol = procedural_volume(16, kind="shell")
    cfg = RenderConfig(max_steps=STEPS, early_exit_alpha=1.1,
                       background=background)
    data = shard_volume(vol.data, mesh)
    imgs = {}
    for ex in ("all_to_all", "ring"):
        step = distributed_plain_step(
            mesh, _tf(), W, H, cfg, comp_cfg=CompositeConfig(exchange=ex))
        imgs[ex] = np.asarray(step(data, vol.origin, vol.spacing, _cam()))
    np.testing.assert_array_equal(imgs["ring"], imgs["all_to_all"])


def test_ring_plain_mxu_step_matches_all_to_all():
    """Plain MXU exchange parity (intermediate-grid image + axcam)."""
    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.parallel.pipeline import (
        distributed_plain_step_mxu)

    mesh = make_mesh(N)
    vol = procedural_volume(16, kind="blobs")
    cam = _cam()
    spec = slicer.make_spec(cam, vol.data.shape,
                            SliceMarchConfig(matmul_dtype="f32", scale=1.5),
                            multiple_of=N)
    data = shard_volume(vol.data, mesh)
    imgs = {}
    for ex in ("all_to_all", "ring"):
        step = distributed_plain_step_mxu(
            mesh, _tf(), spec, comp_cfg=CompositeConfig(exchange=ex))
        img, _ = step(data, vol.origin, vol.spacing, cam)
        imgs[ex] = np.asarray(img)
    np.testing.assert_array_equal(imgs["ring"], imgs["all_to_all"])


def test_ring_hybrid_step_matches_all_to_all():
    """Hybrid volume+particle frame: the VDI half composites under the
    configured exchange; the splat half is exchange-independent — whole
    frames must match."""
    import jax

    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.parallel.pipeline import (
        distributed_hybrid_step_mxu)
    from scenery_insitu_tpu.parallel.particles import shard_particles

    mesh = make_mesh(N)
    vol = procedural_volume(16, kind="blobs")
    cam = _cam()
    vcfg = VDIConfig(max_supersegments=6, adaptive_iters=2)
    spec = slicer.make_spec(cam, vol.data.shape,
                            SliceMarchConfig(matmul_dtype="f32", scale=1.5),
                            multiple_of=N)
    key = jax.random.PRNGKey(7)
    pos = jax.random.uniform(key, (64, 3), minval=-0.8, maxval=0.8)
    vel = jax.random.normal(jax.random.PRNGKey(8), (64, 3)) * 0.1
    data = shard_volume(vol.data, mesh)
    p = shard_particles(pos, mesh)
    v = shard_particles(vel, mesh)
    imgs = {}
    for ex in ("all_to_all", "ring"):
        ccfg = CompositeConfig(max_output_supersegments=8, adaptive_iters=2,
                               exchange=ex)
        step = distributed_hybrid_step_mxu(mesh, _tf(), spec, vcfg, ccfg,
                                           radius=0.05, stamp=3)
        img, _ = step(data, vol.origin, vol.spacing, p, v, cam)
        imgs[ex] = np.asarray(img)
    np.testing.assert_allclose(imgs["ring"], imgs["all_to_all"],
                               atol=1e-6, rtol=0)


def test_ring_build_emits_obs_counters():
    """The ring build mints per-hop counters and a modeled-traffic event
    (docs/OBSERVABILITY.md) at trace time."""
    from scenery_insitu_tpu import obs

    rec = obs.Recorder(enabled=True)
    prev = obs.set_recorder(rec)
    try:
        mesh = make_mesh(4)
        vol = procedural_volume(16, kind="blobs")
        step = distributed_vdi_step(
            mesh, _tf(), W, H,
            VDIConfig(max_supersegments=6, adaptive_iters=2),
            CompositeConfig(max_output_supersegments=8, exchange="ring"),
            max_steps=STEPS)
        step(shard_volume(vol.data, mesh), vol.origin, vol.spacing, _cam())
    finally:
        obs.set_recorder(prev)
    assert rec.counters.get("ring_exchange_builds", 0) >= 1
    assert rec.counters.get("ring_steps_built", 0) >= 3   # n-1 hops
    builds = [e for e in rec.events
              if e.get("name") == "ring_exchange_build"]
    assert builds and "traffic" in builds[0]["attrs"]
    t = builds[0]["attrs"]["traffic"]
    assert t["peak_stream_slots_per_pixel"] == 4 * 6      # lossless = N*K


def test_modeled_exchange_traffic_memory_model():
    """The N·K → ring_slots+K working-set reduction the docs claim.
    stream_bytes_per_rank covers the merge working set PLUS the
    resegmented k_out-slot output write (k_out used to be echoed but
    never accounted)."""
    a2a = modeled_exchange_traffic(8, 16, 720, 1280, k_out=16)
    ring = modeled_exchange_traffic(8, 16, 720, 1280, k_out=16,
                                    mode="ring", ring_slots=16)
    assert a2a["peak_stream_slots_per_pixel"] == 8 * 16
    assert ring["peak_stream_slots_per_pixel"] == 2 * 16
    assert ring["ici_bytes_per_rank"] == a2a["ici_bytes_per_rank"]
    px = 720 * (1280 // 8)
    assert a2a["stream_bytes_per_rank"] == (8 * 16 + 16) * px * 24
    assert ring["stream_bytes_per_rank"] == (2 * 16 + 16) * px * 24
