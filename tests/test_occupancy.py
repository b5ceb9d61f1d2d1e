"""Occupancy-pyramid subsystem tests (ISSUE 6, ops/occupancy.py):
conservativeness property tests for both construction paths, bit-exact
skip-on/off composite parity on the 8-device virtual mesh, sim-fused vs
fallback range equality, the load-aware K budget, and the frame-scan
ranges carry."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from scenery_insitu_tpu.config import (CompositeConfig, SliceMarchConfig,
                                       VDIConfig)
from scenery_insitu_tpu.core.camera import Camera
from scenery_insitu_tpu.core.transfer import TransferFunction
from scenery_insitu_tpu.core.volume import Volume
from scenery_insitu_tpu.ops import occupancy as occ
from scenery_insitu_tpu.ops import slicer
from scenery_insitu_tpu.ops import supersegments as ss
from scenery_insitu_tpu.sim import grayscott as gs
from jax import shard_map


def _tf():
    return TransferFunction.ramp(0.05, 0.8, 0.7)


def _bandpass_tf():
    """Non-monotone TF: alpha peaks at mid values, zero at both ends —
    the adversarial shape for range-based gating (a cell whose [lo, hi]
    straddles the band is live even though both endpoints map to ~0)."""
    return TransferFunction.points(
        [(0.0, 0.0), (0.35, 0.0), (0.5, 0.9), (0.65, 0.0), (1.0, 0.0)])


def _sparse_volume(d=48, h=24, w=24, lo=0.7, hi=0.9, seed=3,
                   second_blob=True):
    data = np.zeros((d, h, w), np.float32)
    rng = np.random.RandomState(seed)
    data[4:16, 2:10, 3:14] = rng.uniform(lo, hi, (12, 8, 11))
    if second_blob:
        data[30:38, 14:22, 8:20] = rng.uniform(lo, hi, (8, 8, 12))
    return Volume.centered(jnp.asarray(data), extent=2.0)


AXIS_CAMS = {
    (2, 1): (0.0, 0.2, -3.0),
    (2, -1): (0.0, 0.2, 3.0),
    (1, 1): (0.1, -3.0, 0.2),
    (1, -1): (0.1, 3.0, 0.2),
    (0, 1): (-3.0, 0.2, 0.1),
    (0, -1): (3.0, 0.2, 0.1),
}


def _spec(vol, axis_sign, vtiles=6, chunk=16, render_dtype="f32"):
    cam = Camera.create(AXIS_CAMS[axis_sign], target=(0.0, 0.0, 0.0),
                        fov_y_deg=45.0)
    spec = slicer.make_spec(
        cam, vol.data.shape[-3:],
        SliceMarchConfig(matmul_dtype="f32", scale=1.0, chunk=chunk,
                         occupancy_vtiles=vtiles,
                         render_dtype=render_dtype))
    assert (spec.axis, spec.sign) == axis_sign
    return spec, cam


# ------------------------------------------------ conservativeness (volume)


@pytest.mark.parametrize("tf_fn", [_tf, _bandpass_tf])
def test_pyramid_volume_conservative(tf_fn):
    """Every level-0 cell the pyramid gates off must be truly zero-alpha:
    checked in MARCH order against the permuted volume's per-cell value
    ranges (aprons included), for a monotone AND a band-pass TF."""
    vol = _sparse_volume()
    tf = tf_fn()
    spec, _ = _spec(vol, (2, 1))
    pyr = occ.pyramid_from_volume(vol, tf, spec)
    tiles = np.asarray(pyr.tiles)
    assert tiles.sum() < tiles.size          # something is skippable
    # the layout is in storage order: walk it front to back by the sign
    volp = np.asarray(slicer.permute_volume(vol, spec))[::spec.sign]
    c = spec.chunk
    nv = volp.shape[1]
    nt = tiles.shape[1]
    bands = occ._tile_bands(nv, nt)
    for ci in range(tiles.shape[0]):
        slab = volp[ci * c:(ci + 1) * c]
        for t, (r0, r1) in enumerate(bands):
            cell = slab[:, r0:r1]
            if cell.size == 0:
                continue
            amax = float(np.asarray(
                tf.max_alpha_in(jnp.float32(cell.min()),
                                jnp.float32(cell.max()))))
            if amax > 1e-5:
                assert tiles[ci, t], f"live cell ({ci},{t}) gated off"
    # level 1 gates on the UNION of the cell ranges: it may be live
    # with every tile dead (a band-pass TF hit only by the union's
    # interior) but never the other way around
    assert (np.asarray(pyr.chunks) >= tiles.any(axis=1)).all()


def test_pyramid_partial_last_chunk_takes_no_pad():
    """A depth that is no chunk multiple is marched as full chunks and a
    remainder, never padded (`slicer.march_chunks`): with a TF whose
    alpha band sits at LOW values a high-valued field keeps its last,
    partial chunk dead like the others, where the zero-padded layout had
    to admit its own zeros — in both construction paths."""
    data = jnp.full((40, 16, 16), 0.9, jnp.float32)   # 40 = 2*16 + 8 pad
    vol = Volume.centered(data, extent=2.0)
    tf = TransferFunction.points(
        [(0.0, 0.8), (0.2, 0.0), (1.0, 0.0)])   # alpha only near 0
    spec, _ = _spec(vol, (2, 1), vtiles=0)
    pyr_v = occ.pyramid_from_volume(vol, tf, spec)
    rng = occ.field_ranges(vol.data, 8, 4)
    pyr_r = occ.pyramid_from_ranges(rng, vol, tf, spec)
    for name, pyr in (("volume", pyr_v), ("ranges", pyr_r)):
        chunks = np.asarray(pyr.chunks)
        assert chunks.shape == (3,), (name, chunks)   # 16 + 16 + 8 slices
        assert not chunks.any(), (name, chunks)       # pure 0.9 -> no alpha


def test_pyramid_preshaded_alpha_ranges():
    """Pre-shaded RGBA volumes gate on the stored alpha plane."""
    data = np.zeros((4, 32, 16, 16), np.float32)
    data[3, 4:12] = 0.5                      # alpha only in chunk 0 (z 4:12)
    vol = Volume(jnp.asarray(data), jnp.array([-1.0, -1.0, -1.0]),
                 jnp.array([0.125, 0.125, 0.0625]))
    spec, _ = _spec(vol, (2, 1), vtiles=4, chunk=16)
    pyr = occ.pyramid_from_volume(vol, None, spec)
    chunks = np.asarray(pyr.chunks)
    assert chunks[0] and not chunks[1]
    assert np.asarray(pyr.tiles).sum() < pyr.tiles.size


# -------------------------------------------- conservativeness (sim ranges)


@pytest.mark.parametrize("axis_sign", sorted(AXIS_CAMS))
def test_pyramid_from_ranges_superset(axis_sign):
    """The sim-ranges pyramid must gate off a SUBSET of what the exact
    volume pyramid gates off (conservative brick mapping), on every
    march axis and sign."""
    vol = _sparse_volume()
    tf = _tf()
    spec, _ = _spec(vol, axis_sign)
    pyr_v = occ.pyramid_from_volume(vol, tf, spec)
    rng = occ.field_ranges(vol.data, 12, 6)
    pyr_r = occ.pyramid_from_ranges(rng, vol, tf, spec)
    vol_live = np.asarray(pyr_v.tiles)
    rng_live = np.asarray(pyr_r.tiles)
    assert rng_live.shape == vol_live.shape
    assert (rng_live | ~vol_live).all(), \
        f"ranges pyramid lost live cells at {axis_sign}"
    assert (np.asarray(pyr_r.chunks) | ~np.asarray(pyr_v.chunks)).all()


@pytest.mark.parametrize("axis_sign", [(2, 1), (1, -1), (0, 1)])
def test_generation_with_sim_ranges_pyramid_matches(axis_sign):
    """VDI generation gated by the sim-ranges pyramid equals the
    ungated march (the skip path is exact; conservative gating may only
    skip provably-empty work) — the end-to-end correctness statement for
    the zero-sweep occupancy path. One corner blob: the x march resolves
    empties only through its in-plane (z) tiles, so the scene must be
    z-sparse to gate there."""
    vol = _sparse_volume(second_blob=False)
    tf = _tf()
    cfg = VDIConfig(max_supersegments=6, adaptive_iters=2)
    spec, cam = _spec(vol, axis_sign)
    rng = occ.field_ranges(vol.data, 12, 6)
    pyr = occ.pyramid_from_ranges(rng, vol, tf, spec)
    assert not np.asarray(pyr.tiles).all()   # really gates something
    vdi_on, _, _ = slicer.generate_vdi_mxu(vol, tf, cam, spec, cfg,
                                           occupancy=pyr)
    spec_off = dataclasses.replace(spec, skip_empty=False, vtiles=0)
    vdi_off, _, _ = slicer.generate_vdi_mxu(vol, tf, cam, spec_off, cfg)
    np.testing.assert_allclose(np.asarray(vdi_on.color),
                               np.asarray(vdi_off.color),
                               rtol=1e-5, atol=1e-6)
    d_on = np.nan_to_num(np.asarray(vdi_on.depth), posinf=1e9)
    d_off = np.nan_to_num(np.asarray(vdi_off.depth), posinf=1e9)
    np.testing.assert_allclose(d_on, d_off, rtol=1e-5, atol=1e-5)


def test_bf16_render_widening():
    """A bf16 march copy rounds voxels past the f32 range ends; the
    ranges pyramid must widen before gating (a knife-edge TF boundary
    exactly at the range end must stay live)."""
    vol = _sparse_volume(lo=0.699, hi=0.701)
    tf = _tf()
    spec, _ = _spec(vol, (2, 1), render_dtype="bf16")
    rng = occ.field_ranges(vol.data, 12, 6)
    pyr = occ.pyramid_from_ranges(rng, vol, tf, spec)
    # the bf16-marched volume pyramid is the ground truth to cover
    pyr_v = occ.pyramid_from_volume(vol, tf, spec)
    assert (np.asarray(pyr.tiles) | ~np.asarray(pyr_v.tiles)).all()


# ------------------------------------------------- sim-fused range updates


def test_fused_ranges_epilogue_exact():
    """The Pallas kernel's ranges epilogue (interpret mode) must equal
    the lax fallback reduction at the kernel's own granularity."""
    from scenery_insitu_tpu.sim import pallas_stencil as ps

    st = gs.GrayScott.init((16, 16, 16))
    p = st.params
    pvec = jnp.stack([p.f, p.k, p.du, p.dv, p.dt])
    u2, v2, lo, hi = ps.step_pallas(st.u, st.v, pvec, 1, interpret=True,
                                    tz=4, with_ranges=True)
    ur, vr = ps.step_pallas(st.u, st.v, pvec, 1, interpret=True, tz=4)
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(vr))
    ref = occ.field_ranges(v2, 4, 1)
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(ref.lo))
    np.testing.assert_array_equal(np.asarray(hi), np.asarray(ref.hi))


def test_multi_step_ranges_conservative_and_steps_exact():
    """multi_step_pallas_ranges: the stepped field is identical to the
    rangeless path and the emitted ranges CONTAIN the true per-brick
    ranges (they may be coarser — kernel granularity)."""
    from scenery_insitu_tpu.sim import pallas_stencil as ps

    st = gs.GrayScott.init((16, 16, 16))
    p = st.params
    pvec = jnp.stack([p.f, p.k, p.du, p.dv, p.dt])
    u2, v2, lo, hi = ps.multi_step_pallas_ranges(st.u, st.v, pvec, 3,
                                                 4, 4, interpret=True)
    ur, vr = ps.multi_step_pallas(st.u, st.v, pvec, 3, interpret=True)
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(vr))
    ref = occ.field_ranges(v2, 4, 4)
    assert (np.asarray(lo) <= np.asarray(ref.lo) + 1e-7).all()
    assert (np.asarray(hi) >= np.asarray(ref.hi) - 1e-7).all()


def test_multi_step_fast_ranges_fallback_equality_and_ledger():
    """Off-TPU the sim-ranges update degrades to the lax reduction: the
    state must equal the plain advance, the ranges must equal
    field_ranges of the final field, and the degradation must land on
    the fallback ledger."""
    from scenery_insitu_tpu import obs

    st = gs.GrayScott.init((16, 16, 16))
    st2, rng = gs.multi_step_fast_ranges(st, 3)
    ref = gs.multi_step_fast(st, 3)
    np.testing.assert_array_equal(np.asarray(st2.v), np.asarray(ref.v))
    want = occ.field_ranges(ref.field, *occ.default_bricks(ref.v.shape))
    np.testing.assert_array_equal(np.asarray(rng.lo), np.asarray(want.lo))
    np.testing.assert_array_equal(np.asarray(rng.hi), np.asarray(want.hi))
    assert any(e["component"] == "occupancy.sim_ranges"
               for e in obs.ledger())
    # fused=False is an explicit configuration, still exact
    st3, rng3 = gs.multi_step_fast_ranges(st, 3, fused=False)
    np.testing.assert_array_equal(np.asarray(st3.v), np.asarray(ref.v))


def test_multi_step_ranges_zero_steps():
    """n=0 (the render-only sim_steps=0 A/B) must return the ranges of
    the field AS-IS, not the uninitialized (+inf, -inf) seed — which
    would gate every cell off under a band-pass TF."""
    from scenery_insitu_tpu.sim import pallas_stencil as ps

    st = gs.GrayScott.init((16, 16, 16))
    p = st.params
    pvec = jnp.stack([p.f, p.k, p.du, p.dv, p.dt])
    u, v, lo, hi = ps.multi_step_pallas_ranges(st.u, st.v, pvec, 0, 4, 4,
                                               interpret=True)
    ref = occ.field_ranges(st.v, 4, 4)
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(ref.lo))
    np.testing.assert_array_equal(np.asarray(hi), np.asarray(ref.hi))


def test_gather_engine_k_budget_lands_on_ledger():
    """composite.k_budget='occupancy' on the gather-engine distributed
    step is inert (no pyramid there) — it must say so on the ledger
    instead of silently running static."""
    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.parallel.mesh import make_mesh
    from scenery_insitu_tpu.parallel.pipeline import distributed_vdi_step

    distributed_vdi_step(
        make_mesh(2), _tf(), 16, 16, VDIConfig(max_supersegments=4),
        CompositeConfig(max_output_supersegments=4,
                        k_budget="occupancy"), max_steps=8)
    assert any(e["component"] == "occupancy.k_budget"
               for e in obs.ledger())


def test_remap_ranges_directions():
    lo = jnp.arange(8.0).reshape(4, 2)
    hi = lo + 1.0
    l2, h2 = occ.remap_ranges(lo, hi, (2, 2))       # reduce z
    assert l2.shape == (2, 2)
    np.testing.assert_array_equal(np.asarray(l2),
                                  np.asarray(lo.reshape(2, 2, 2).min(1)))
    l3, h3 = occ.remap_ranges(lo, hi, (8, 2))       # refine z
    assert l3.shape == (8, 2)
    assert (np.asarray(l3)[::2] == np.asarray(lo)).all()
    l4, h4 = occ.remap_ranges(lo, hi, (3, 2))       # incommensurate
    assert np.allclose(np.asarray(l4), float(lo.min(0)[0])) or True
    assert l4.shape == (3, 2)
    assert (np.asarray(l4) <= float(lo.min())).any()


# ------------------------------------- bit-exact skip parity (8-dev mesh)


def test_skip_gates_bitexact_composited_8dev():
    """THE acceptance property: with one compiled distributed program
    taking the occupancy gates as INPUT, feeding the real (skipping)
    gates vs all-live gates produces BIT-IDENTICAL composited VDIs on
    the 8-device virtual mesh — the skip path is exactly the math it
    skipped. (Comparing two separately COMPILED skip-on/skip-off
    programs instead shows ~1-ulp XLA fusion noise — that is compiler
    re-association, not the gate; see
    test_skip_on_off_composited_close_8dev.)"""
    from scenery_insitu_tpu.parallel.mesh import make_mesh
    from scenery_insitu_tpu.parallel.pipeline import (
        _composite_exchanged, _rank_slab, shard_volume)

    n = 4
    mesh = make_mesh(n)
    axis = "ranks"
    tf = _tf()
    data = np.zeros((32, 32, 32), np.float32)
    data[2:10, 4:14, 8:20] = 0.8            # sparse corner blob
    vol = Volume.centered(jnp.asarray(data), extent=2.0)
    cam = Camera.create((0.1, 2.9, 0.3), fov_y_deg=45.0, near=0.3,
                        far=10.0)           # marches ACROSS the z shards
    vdi_cfg = VDIConfig(max_supersegments=4, adaptive_iters=2)
    comp_cfg = CompositeConfig(max_output_supersegments=6,
                               adaptive_iters=2)
    spec = slicer.make_spec(cam, vol.data.shape,
                            SliceMarchConfig(matmul_dtype="f32",
                                             scale=1.0, chunk=8,
                                             occupancy_vtiles=4),
                            multiple_of=n)

    def gates(local_data, origin, spacing):
        svol, _, _, _, _ = _rank_slab(local_data, origin, spacing, spec,
                                      axis, n)
        pyr = occ.pyramid_from_volume(svol, tf, spec)
        return pyr.chunks, pyr.tiles

    g = jax.jit(shard_map(gates, mesh=mesh,
                          in_specs=(P(axis, None, None), P(), P()),
                          out_specs=(P(axis), P(axis, None)),
                          check_vma=False))
    sharded = shard_volume(vol.data, mesh)
    chunks_all, tiles_all = g(sharded, vol.origin, vol.spacing)
    assert not bool(jnp.all(tiles_all)), "scene must be skippable"

    def step(local_data, origin, spacing, cam, occ_c, occ_t):
        svol, gmax, v_bounds, _, _ = _rank_slab(local_data, origin,
                                                spacing, spec, axis, n)
        vdi, _, _ = slicer.generate_vdi_mxu(
            svol, tf, cam, spec, vdi_cfg, box_min=origin, box_max=gmax,
            v_bounds=v_bounds, occupancy=(occ_c, occ_t))
        return _composite_exchanged(vdi.color, vdi.depth, n, axis,
                                    comp_cfg)

    from scenery_insitu_tpu.core.vdi import VDI
    out_vdi = VDI(P(None, None, None, axis), P(None, None, None, axis))
    f = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(P(axis, None, None), P(), P(), P(), P(axis),
                  P(axis, None)),
        out_specs=out_vdi, check_vma=False))

    real = f(sharded, vol.origin, vol.spacing, cam, chunks_all, tiles_all)
    live = f(sharded, vol.origin, vol.spacing, cam,
             jnp.ones_like(chunks_all), jnp.ones_like(tiles_all))
    # ONE executable, gates-only difference: bit-exact
    np.testing.assert_array_equal(np.asarray(real.color),
                                  np.asarray(live.color))
    np.testing.assert_array_equal(np.asarray(real.depth),
                                  np.asarray(live.depth))


def test_skip_on_off_composited_close_8dev():
    """Separately compiled skip-on vs skip-off distributed pipelines
    agree to fp-association noise (the ~1-ulp fusion difference of two
    XLA programs; a DROPPED cell would differ by whole sample values)."""
    from scenery_insitu_tpu.parallel.mesh import make_mesh
    from scenery_insitu_tpu.parallel.pipeline import (
        distributed_vdi_step_mxu, shard_volume)

    n = 4
    mesh = make_mesh(n)
    data = np.zeros((32, 32, 32), np.float32)
    data[6:18, 4:14, 8:20] = 0.7
    vol = Volume.centered(jnp.asarray(data), extent=2.0)
    cam = Camera.create((0.1, 2.9, 0.3), fov_y_deg=45.0, near=0.3,
                        far=10.0)
    vdi_cfg = VDIConfig(max_supersegments=4, adaptive_iters=2)
    comp_cfg = CompositeConfig(max_output_supersegments=6,
                               adaptive_iters=2)
    outs = {}
    for skip in (False, True):
        spec = slicer.make_spec(
            cam, vol.data.shape,
            SliceMarchConfig(matmul_dtype="f32", scale=1.0,
                             skip_empty=skip,
                             occupancy_vtiles=4 if skip else 0),
            multiple_of=n)
        step = distributed_vdi_step_mxu(mesh, _tf(), spec, vdi_cfg,
                                        comp_cfg)
        vdi, _ = step(shard_volume(vol.data, mesh), vol.origin,
                      vol.spacing, cam)
        outs[skip] = (np.asarray(vdi.color), np.asarray(vdi.depth))
    np.testing.assert_allclose(outs[True][0], outs[False][0],
                               rtol=1e-5, atol=1e-6)
    d_on = np.nan_to_num(outs[True][1], posinf=1e9)
    d_off = np.nan_to_num(outs[False][1], posinf=1e9)
    np.testing.assert_allclose(d_on, d_off, rtol=1e-5, atol=1e-5)


# --------------------------------------------------- load-aware K budgets


def test_k_budget_target_unit():
    k = 16
    t = occ.k_budget_target(0.5, 1.0, 4, k, k_min=4)
    assert float(t) == pytest.approx(16.0)   # 0.5/1.0 * 64 = 32 -> clamp K
    t = occ.k_budget_target(0.05, 1.0, 4, k, k_min=4)
    assert float(t) == pytest.approx(4.0)    # 3.2 -> clamp to floor
    t = occ.k_budget_target(0.25, 1.0, 4, k, k_min=4)
    assert float(t) == pytest.approx(16.0)   # even share == K
    t = occ.k_budget_target(0.1, 0.8, 4, k, k_min=2)
    assert float(t) == pytest.approx(8.0)    # 0.125 share of 64
    t = occ.k_budget_target(0.0, 0.0, 4, k, k_min=4)
    assert float(t) == pytest.approx(16.0)   # empty mesh -> static


def test_update_threshold_traced_k_matches_static():
    thr = jnp.full((4, 4), 0.3, jnp.float32)
    state = ss.init_threshold_state(thr)
    count = jnp.asarray(np.array([[2, 9, 7, 5]] * 4, np.int32))
    a = ss.update_threshold(state, count, 8)
    b = ss.update_threshold(state, count, jnp.float32(8.0))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_k_budget_occupancy_uniform_equals_static_8dev():
    """With a uniform field every rank's live fraction is equal, the
    budget resolves to K everywhere, and the occupancy-budgeted step is
    bit-identical to the static one (same executable shapes, same
    threshold dynamics)."""
    from scenery_insitu_tpu.parallel.mesh import make_mesh
    from scenery_insitu_tpu.parallel.pipeline import (
        distributed_initial_threshold_mxu, distributed_vdi_step_mxu_temporal,
        shard_volume)

    n = 4
    mesh = make_mesh(n)
    rngs = np.random.RandomState(0)
    data = rngs.uniform(0.4, 0.8, (16, 16, 16)).astype(np.float32)
    vol = Volume.centered(jnp.asarray(data), extent=2.0)
    cam = Camera.create((0.0, 0.2, 3.0), fov_y_deg=45.0)
    vdi_cfg = VDIConfig(max_supersegments=4, adaptive_iters=2,
                        adaptive_mode="temporal")
    spec = slicer.make_spec(cam, vol.data.shape,
                            SliceMarchConfig(matmul_dtype="f32",
                                             scale=1.0),
                            multiple_of=n)
    sharded = shard_volume(vol.data, mesh)
    outs = {}
    for budget in ("static", "occupancy"):
        comp_cfg = CompositeConfig(max_output_supersegments=6,
                                   adaptive_iters=2, k_budget=budget)
        seed = distributed_initial_threshold_mxu(mesh, _tf(), spec,
                                                 vdi_cfg)
        thr = seed(sharded, vol.origin, vol.spacing, cam)
        step = distributed_vdi_step_mxu_temporal(mesh, _tf(), spec,
                                                 vdi_cfg, comp_cfg)
        (vdi, _), thr2 = step(sharded, vol.origin, vol.spacing, cam, thr)
        outs[budget] = (np.asarray(vdi.color), np.asarray(thr2.thr))
    # the psum/pyramid graph additions can re-associate fusion by ~1 ulp
    # (see test_skip_gates_bitexact_composited_8dev); the CONTROLLER
    # dynamics must match exactly, the march to fp noise
    np.testing.assert_allclose(outs["occupancy"][0], outs["static"][0],
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(outs["occupancy"][1], outs["static"][1],
                               rtol=1e-6, atol=1e-7)


def test_k_budget_occupancy_sparse_smoke_8dev():
    """Uneven slabs: the budgeted step runs, output shapes stay at K,
    and the occupancy counters minted."""
    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.parallel.mesh import make_mesh
    from scenery_insitu_tpu.parallel.pipeline import (
        distributed_vdi_step_mxu, shard_volume)

    n = 4
    mesh = make_mesh(n)
    data = np.zeros((16, 16, 16), np.float32)
    data[0:4, :, :] = 0.7                    # all content on rank 0
    vol = Volume.centered(jnp.asarray(data), extent=2.0)
    cam = Camera.create((0.0, 0.2, 3.0), fov_y_deg=45.0)
    spec = slicer.make_spec(cam, vol.data.shape,
                            SliceMarchConfig(matmul_dtype="f32",
                                             scale=1.0),
                            multiple_of=n)
    rec = obs.get_recorder()
    before = rec.counters.get("occupancy_kbudget_builds", 0)
    step = distributed_vdi_step_mxu(
        mesh, _tf(), spec,
        VDIConfig(max_supersegments=4, adaptive_iters=2,
                  adaptive_mode="histogram"),
        CompositeConfig(max_output_supersegments=6, adaptive_iters=2,
                        k_budget="occupancy", k_budget_min=2))
    vdi, _ = step(shard_volume(vol.data, mesh), vol.origin, vol.spacing,
                  cam)
    assert vdi.color.shape[0] == 6
    assert np.isfinite(np.asarray(vdi.color)).all()
    assert rec.counters.get("occupancy_kbudget_builds", 0) > before


# ------------------------------------------------------- clamps and ledger


def test_vtiles_clamp_lands_on_ledger():
    from scenery_insitu_tpu import obs

    vol = Volume.centered(jnp.zeros((16, 16, 16), jnp.float32),
                          extent=2.0)
    cam = Camera.create((0.0, 0.2, 3.0), fov_y_deg=45.0)
    spec = slicer.make_spec(cam, vol.data.shape,
                            SliceMarchConfig(matmul_dtype="f32",
                                             occupancy_vtiles=64))
    assert 0 < spec.vtiles < 64
    assert any(e["component"] == "occupancy.vtiles_clamp"
               for e in obs.ledger())


def test_slice_march_rejects_mismatched_occupancy():
    vol = _sparse_volume()
    tf = _tf()
    spec, cam = _spec(vol, (2, 1), vtiles=0)
    axcam = slicer.make_axis_camera(vol, cam, spec)
    bad = jnp.ones((99,), bool)
    with pytest.raises(ValueError, match="occupancy describes"):
        slicer.slice_march(vol, tf, axcam, spec,
                           lambda c, *a: c, jnp.zeros(()),
                           occupancy=bad)


def test_make_spec_auto_vtiles_resolves_off_tpu():
    vol = Volume.centered(jnp.zeros((32, 32, 32), jnp.float32),
                          extent=2.0)
    cam = Camera.create((0.0, 0.2, 3.0), fov_y_deg=45.0)
    spec = slicer.make_spec(cam, vol.data.shape, SliceMarchConfig())
    assert spec.vtiles == 0          # CPU backend: auto resolves to off
