"""Multi-resolution brick maps (LODConfig; docs/PERF.md "LOD
marching"): per-brick refinement levels on BrickMap, the
reslab_bricks_lod pooled materialization, the level planner
(parallel/lod.py — screen-space error, empty coarsening, hysteresis,
the TF-straddle gate), the coarse MXU march, and the session replan
loop.

Parity gates, and why each is what it is:
- the all-level-0 LOD map is BITWISE the pre-LOD brick path on the
  gather builder and the MXU builders: level 0 units take the exact
  legacy code path (same bands, same camera object, default
  step_scale), so this is a structural identity the tests pin down as
  a regression gate (the CI `lod` lane runs it).
- coarse levels on EMPTY bricks match the even frame at the 1e-5 MXU
  gate: pooling air is exact, the march of a zero brick emits nothing
  at any level.
- coarse levels on a SMOOTH field hold a PSNR floor vs the exact
  frame: reshape-mean pooling + the step_scale opacity re-correction
  approximate the fine march; the committed bench ladder
  (benchmarks/results/lod_ab_r16_cpu.json) carries the quantitative
  claim, this test guards against regressions that would tank it.
- the TF-straddle gate is a PROPERTY: no brick whose sampled value
  range crosses an opacity edge is ever assigned level > 0 — under
  random ranges/edges and after a steered TF update (scenario zoo
  path).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scenery_insitu_tpu.config import (CompositeConfig, LODConfig,
                                       SliceMarchConfig, VDIConfig)
from scenery_insitu_tpu.core.camera import Camera
from scenery_insitu_tpu.core.transfer import TransferFunction, opacity_edges
from scenery_insitu_tpu.ops.occupancy import z_range_profile
from scenery_insitu_tpu.parallel import bricks as bk
from scenery_insitu_tpu.parallel import lod as lodm
from scenery_insitu_tpu.parallel.mesh import make_mesh, reslab_bricks_lod
from scenery_insitu_tpu.parallel.pipeline import (distributed_vdi_step,
                                                  distributed_vdi_step_mxu,
                                                  shard_volume)
from jax import shard_map

N = 8
D = 32
HW = 16
ATOL = 1e-5

OWNER = (3, 0, 5, 1, 4, 7, 2, 6)
ISLANDS = (0, 0, 1, 2, 3, 4, 5, 6)


def _cam(eye=(0.0, 0.2, 4.0)):
    return Camera.create(eye, fov_y_deg=50.0, near=0.5, far=20.0)


def _tf():
    return TransferFunction.ramp(0.05, 0.8, 0.7)


def _scene():
    """The test_bricks.py blob scene: brick 6 of an 8-brick split
    (rows 24..27) is EMPTY."""
    data = np.zeros((D, HW, HW), np.float32)
    blobs = [(1, 3, 0.3), (5, 7, 0.5), (9, 11, 0.7), (13, 15, 0.4),
             (17, 19, 0.6), (21, 23, 0.8), (29, 31, 0.45)]
    for a, b, v in blobs:
        data[a:b] = v
    vox = 2.0 / D
    origin = jnp.asarray([-HW * vox / 2, -HW * vox / 2, -1.0], jnp.float32)
    spacing = jnp.full((3,), vox, jnp.float32)
    return jnp.asarray(data), origin, spacing


def _smooth_scene():
    """Gently varying field — the coarse-march quality scene."""
    z = np.arange(D)[:, None, None] / D
    y = np.arange(HW)[None, :, None] / HW
    x = np.arange(HW)[None, None, :] / HW
    data = (0.45 + 0.18 * np.sin(2 * np.pi * z)
            * np.cos(np.pi * y) * np.cos(np.pi * x)).astype(np.float32)
    vox = 2.0 / D
    origin = jnp.asarray([-HW * vox / 2, -HW * vox / 2, -1.0], jnp.float32)
    spacing = jnp.full((3,), vox, jnp.float32)
    return jnp.asarray(data), origin, spacing


def _mxu_spec(cam, **cfg_kw):
    from scenery_insitu_tpu.ops import slicer

    return slicer.make_spec(cam, (D, HW, HW),
                            SliceMarchConfig(matmul_dtype="f32", scale=2.0,
                                             **cfg_kw),
                            multiple_of=N)


def _cfgs(rebalance="bricks", **comp_kw):
    return (VDIConfig(max_supersegments=6, adaptive_iters=2),
            CompositeConfig(max_output_supersegments=12, adaptive_iters=2,
                            rebalance=rebalance, **comp_kw))


def _assert_vdi_close(a, b, atol=ATOL):
    ac, ad = np.asarray(a[0]), np.asarray(a[1])
    bc, bd = np.asarray(b[0]), np.asarray(b[1])
    np.testing.assert_allclose(ac, bc, atol=atol, rtol=0)
    assert (np.isinf(ad) == np.isinf(bd)).all()
    fin = np.isfinite(ad)
    np.testing.assert_allclose(ad[fin], bd[fin], atol=atol, rtol=0)


def _psnr(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    mse = float(np.mean((a - b) ** 2))
    if mse == 0.0:
        return np.inf
    return 10.0 * np.log10(1.0 / mse)


# ------------------------------------------------------------ config/units


def test_lodconfig_validation():
    LODConfig(enabled=True, max_level=3)
    with pytest.raises(ValueError, match="max_level"):
        LODConfig(max_level=-1)
    with pytest.raises(ValueError, match="max_level"):
        LODConfig(max_level=9)
    with pytest.raises(ValueError, match="error_px"):
        LODConfig(error_px=0.0)
    with pytest.raises(ValueError, match="hysteresis"):
        LODConfig(hysteresis=1.0)


def test_brickmap_level_field_and_helpers():
    bm = bk.BrickMap(D, N, OWNER)                 # no levels -> all zero
    assert bm.level == (0,) * 8
    assert bm.max_level == 0 and bm.levels_present() == (0,)
    assert bm.total_slots == bm.slots

    lv = (0, 1, 0, 2, 0, 1, 0, 0)
    bml = bk.BrickMap(D, N, OWNER, lv)
    assert bml.max_level == 2
    assert bml.levels_present() == (0, 1, 2)
    assert not bml.is_even_convex()
    # per-level slot counts are GLOBAL maxima (SPMD shape uniformity)
    for lvl in bml.levels_present():
        t = bml.start_table_at(lvl)
        assert t.shape == (N, bml.slots_at(lvl))
    assert bml.total_slots == sum(bml.slots_at(l)
                                  for l in bml.levels_present())
    # level-2 brick is brick 3 (owner 1): its table row has its start
    t2 = bml.start_table_at(2)
    assert t2[1].max() == 3 * bml.brick_depth
    assert (t2[[0, 2, 3, 4, 5, 6, 7]] == -1).all()

    # with_levels swaps levels, keeps ownership
    assert bml.with_levels((0,) * 8).level == (0,) * 8
    # permute carries levels with the map
    assert bml.permute(tuple(range(N))).level == lv


def test_brickmap_level_validation():
    with pytest.raises(ValueError, match="level"):
        bk.BrickMap(D, N, OWNER, (0,) * 7)        # wrong length
    with pytest.raises(ValueError, match="level"):
        bk.BrickMap(D, N, OWNER, (0, -1) + (0,) * 6)
    # brick depth 4 cannot host a level-3 (f=8) brick
    with pytest.raises(ValueError, match="divide"):
        bk.BrickMap(D, N, OWNER, (3,) + (0,) * 7)


def test_steal_plan_carries_levels():
    lv = (0, 1, 0, 2, 0, 1, 0, 0)
    bm = bk.BrickMap(D, N, OWNER, lv)
    prof = np.zeros(8)
    prof[:2] = 1.0
    work = bk.brick_work(prof, D, 8)
    out = bk.steal_plan(bm, work, max_moves=2, hysteresis=0.0)
    assert out.level == lv


def test_opacity_edges_and_range_profile():
    tf = _tf()
    edges = opacity_edges(tf)
    np.testing.assert_allclose(edges, [0.05, 0.8], atol=1e-6)
    # padding knots (x=2) and zero-slope knots never appear
    assert (edges <= 1.0).all()

    data, _, _ = _scene()
    lo, hi = z_range_profile(data, nzb=8)
    lo, hi = np.asarray(lo), np.asarray(hi)
    assert lo.shape == (8,) and hi.shape == (8,)
    assert lo[6] == 0.0 and hi[6] == 0.0           # empty brick
    assert hi[1] >= 0.5                            # blob (5,7,0.5)


def test_per_brick_regrid():
    prof = np.arange(16, dtype=np.float64)
    np.testing.assert_allclose(lodm.per_brick(prof, 8, "mean"),
                               prof.reshape(8, 2).mean(1))
    np.testing.assert_allclose(lodm.per_brick(prof, 8, "min"),
                               prof.reshape(8, 2).min(1))
    np.testing.assert_allclose(lodm.per_brick(prof, 32, "mean"),
                               np.repeat(prof, 2))
    with pytest.raises(ValueError, match="nest"):
        lodm.per_brick(prof, 6)


def test_admissible_max_level():
    assert lodm.admissible_max_level(4, 16, 16, 8) == 2   # bz=4 caps f=4
    assert lodm.admissible_max_level(8, 16, 16, 2) == 2   # cfg caps
    assert lodm.admissible_max_level(8, 16, 16, 8) == 3   # bz=8 caps f=8
    assert lodm.admissible_max_level(4, 2, 16, 8) == 1    # H=2 caps f=2


def _plan_kw(dims=(HW, HW, D), eye=(0.0, 0.0, 4.0), height_px=64):
    vox = 2.0 / D
    return dict(dims=dims,
                origin=np.asarray([-dims[0] * vox / 2, -dims[1] * vox / 2,
                                   -1.0]),
                spacing=np.full(3, vox), eye=np.asarray(eye),
                fov_y=np.deg2rad(50.0), height_px=height_px)


def test_select_levels_screen_error_monotone_with_distance():
    nb = 8
    live = np.ones(nb)
    lo = np.full(nb, 0.3)
    hi = np.full(nb, 0.4)                          # no straddle of 0.05/0.8
    cfg = LODConfig(enabled=True, max_level=2, error_px=1.0,
                    coarsen_empty=False)
    near = lodm.select_levels(live, lo, hi, opacity_edges(_tf()),
                              cfg=cfg, **_plan_kw(eye=(0, 0, 2.5)))
    far = lodm.select_levels(live, lo, hi, opacity_edges(_tf()),
                             cfg=cfg, **_plan_kw(eye=(0, 0, 60.0)))
    assert all(f >= n for f, n in zip(far, near))
    assert max(far) > 0                            # far away coarsens
    # a huge pixel budget coarsens even near
    loose = LODConfig(enabled=True, max_level=2, error_px=1e4,
                      coarsen_empty=False)
    lv = lodm.select_levels(live, lo, hi, opacity_edges(_tf()),
                            cfg=loose, **_plan_kw(eye=(0, 0, 2.5)))
    assert lv == (2,) * nb


def test_select_levels_empty_bricks_coarsen():
    nb = 8
    live = np.zeros(nb)
    live[2] = 0.5
    lo = np.zeros(nb)
    hi = np.zeros(nb)
    lo[2], hi[2] = 0.3, 0.4
    cfg = LODConfig(enabled=True, max_level=2, error_px=0.01)
    lv = lodm.select_levels(live, lo, hi, opacity_edges(_tf()),
                            cfg=cfg, **_plan_kw(eye=(0, 0, 2.5)))
    # the tight error budget keeps occupied bricks fine; air coarsens
    assert lv[2] == 0
    assert all(l == 2 for i, l in enumerate(lv) if i != 2)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_select_levels_tf_straddle_property(seed):
    """PROPERTY: no brick whose sampled value range crosses an opacity
    edge is ever assigned level > 0 — whatever the camera, occupancy
    or hysteresis state says."""
    rng = np.random.default_rng(seed)
    nb = 16
    lo = rng.uniform(0.0, 0.9, nb)
    hi = lo + rng.uniform(0.0, 0.5, nb)
    live = rng.uniform(0.0, 1.0, nb)
    edges = opacity_edges(_tf())
    cfg = LODConfig(enabled=True, max_level=2, error_px=1e4)
    prev = tuple(int(x) for x in rng.integers(0, 3, nb))
    for p in (None, prev):
        lv = lodm.select_levels(live, lo, hi, edges, cfg=cfg, prev=p,
                                **_plan_kw(eye=(0, 0, 50.0)))
        for i in range(nb):
            straddles = any(lo[i] - cfg.tf_edge_eps < e
                            < hi[i] + cfg.tf_edge_eps for e in edges)
            if straddles:
                assert lv[i] == 0, (i, lo[i], hi[i])


def test_select_levels_hysteresis_coarsens_one_level_per_replan():
    nb = 8
    live = np.ones(nb)
    lo = np.full(nb, 0.3)
    hi = np.full(nb, 0.4)
    cfg = LODConfig(enabled=True, max_level=2, error_px=1e4,
                    coarsen_empty=False, hysteresis=0.2)
    kw = _plan_kw(eye=(0, 0, 50.0))
    edges = opacity_edges(_tf())
    lv0 = lodm.select_levels(live, lo, hi, edges, cfg=cfg, prev=(0,) * nb,
                             **kw)
    assert lv0 == (1,) * nb                        # one step, not two
    lv1 = lodm.select_levels(live, lo, hi, edges, cfg=cfg, prev=lv0, **kw)
    assert lv1 == (2,) * nb
    # refinement is immediate: a near camera snaps straight to 0
    tight = LODConfig(enabled=True, max_level=2, error_px=0.01,
                      coarsen_empty=False, hysteresis=0.2)
    lv2 = lodm.select_levels(live, lo, hi, edges, cfg=tight, prev=lv1,
                             **_plan_kw(eye=(0, 0, 2.5)))
    assert lv2 == (0,) * nb


def test_level_work_scale_and_modeled_flops():
    dims = (HW, HW, D)
    zeros = (0,) * 8
    np.testing.assert_allclose(lodm.level_work_scale(zeros, dims, 32, 32),
                               np.ones(8))
    mixed = (0, 1, 2, 0, 0, 0, 0, 0)
    sc = lodm.level_work_scale(mixed, dims, 32, 32)
    assert sc[0] == 1.0 and sc[1] < 1.0 and sc[2] < sc[1]
    f_exact = lodm.modeled_march_flops(zeros, dims, 32, 32)
    f_lod = lodm.modeled_march_flops(mixed, dims, 32, 32)
    assert 0 < f_lod < f_exact
    # the headline ratio the bench reports is exact/lod
    assert f_exact / lodm.modeled_march_flops((2,) * 8, dims, 32, 32) > 8


# ------------------------------------------------------ pooled reslab


def test_reslab_bricks_lod_pools_and_halos():
    mesh = make_mesh(N)
    rng = np.random.default_rng(7)
    data = rng.uniform(0, 1, (D, 8, 8)).astype(np.float32)
    sdata = shard_volume(jnp.asarray(data), mesh)
    lv = (0, 1, 0, 2, 0, 1, 0, 0)
    bm = bk.BrickMap(D, N, ISLANDS, lv)
    from jax.sharding import PartitionSpec as P

    f = jax.jit(shard_map(
        lambda x: reslab_bricks_lod(x, bm, "ranks", h=1), mesh=mesh,
        in_specs=P("ranks", None, None),
        out_specs={l: P("ranks", None, None, None)
                   for l in bm.levels_present()}, check_vma=False))
    out = {l: np.asarray(v) for l, v in f(sdata).items()}
    bz = bm.brick_depth
    for lvl in bm.levels_present():
        fct = 1 << lvl
        table = bm.start_table_at(lvl)
        slots = table.shape[1]
        got = out[lvl].reshape(N, slots, bz // fct + 2, 8 // fct,
                               8 // fct)
        for r in range(N):
            for s in range(slots):
                st = table[r, s]
                if st < 0:
                    assert (got[r, s] == 0).all()
                    continue
                rows = np.clip(np.arange(st - fct, st + bz + fct), 0,
                               D - 1)
                fine = data[rows]
                ref = fine.reshape(bz // fct + 2, fct, 8 // fct, fct,
                                   8 // fct, fct).mean(axis=(1, 3, 5))
                np.testing.assert_allclose(got[r, s], ref, atol=1e-6)


def test_reslab_bricks_lod_rejects_non_dividing_plane():
    mesh = make_mesh(N)
    data = shard_volume(jnp.zeros((D, 6, 6)), mesh)   # 6 % 4 != 0
    bm = bk.BrickMap(D, N, ISLANDS, (2,) + (0,) * 7)
    from jax.sharding import PartitionSpec as P

    with pytest.raises(ValueError, match="lod.max_level"):
        jax.jit(shard_map(
            lambda x: reslab_bricks_lod(x, bm, "ranks"), mesh=mesh,
            in_specs=P("ranks", None, None),
            out_specs={l: P("ranks", None, None, None)
                       for l in bm.levels_present()},
            check_vma=False))(data)


# ------------------------------------------------- march parity + quality


def test_level0_lod_map_bitwise_parity_gather_and_mxu():
    """The CI parity gate: a BrickMap carrying an EXPLICIT all-level-0
    tuple is the pre-LOD brick path — bitwise on the gather builder,
    bitwise on the MXU builder (both resolve to the identical build)."""
    data, origin, spacing = _scene()
    mesh = make_mesh(N)
    sdata = shard_volume(data, mesh)
    cam = _cam()
    bm = bk.BrickMap(D, N, OWNER)
    bm0 = bk.BrickMap(D, N, OWNER, (0,) * 8)
    assert bm0.max_level == 0 and bm0 == bm

    vc, cc = _cfgs()
    g = distributed_vdi_step(mesh, _tf(), HW, HW, vc, cc, max_steps=48,
                             bricks=bm)(sdata, origin, spacing, cam)
    vc, cc = _cfgs()
    g0 = distributed_vdi_step(mesh, _tf(), HW, HW, vc, cc, max_steps=48,
                              bricks=bm0)(sdata, origin, spacing, cam)
    np.testing.assert_array_equal(np.asarray(g.color), np.asarray(g0.color))
    np.testing.assert_array_equal(np.asarray(g.depth), np.asarray(g0.depth))

    spec = _mxu_spec(cam)
    vc, cc = _cfgs()
    m, _ = distributed_vdi_step_mxu(mesh, _tf(), spec, vc, cc, bricks=bm)(
        sdata, origin, spacing, cam)
    vc, cc = _cfgs()
    m0, _ = distributed_vdi_step_mxu(mesh, _tf(), spec, vc, cc,
                                     bricks=bm0)(sdata, origin, spacing,
                                                 cam)
    np.testing.assert_array_equal(np.asarray(m.color), np.asarray(m0.color))
    np.testing.assert_array_equal(np.asarray(m.depth), np.asarray(m0.depth))


def test_mxu_coarse_empty_bricks_match_even():
    """Coarsening an EMPTY brick is exact: the mixed-level frame equals
    the even frame at the MXU gate (pooled air is air; a dead brick
    emits nothing at any level)."""
    data, origin, spacing = _scene()
    mesh = make_mesh(N)
    sdata = shard_volume(data, mesh)
    cam = _cam()
    spec = _mxu_spec(cam)
    vc, cc = _cfgs(rebalance="even")
    even, _ = distributed_vdi_step_mxu(mesh, _tf(), spec, vc, cc)(
        sdata, origin, spacing, cam)
    lv = (0, 0, 0, 0, 0, 0, 2, 0)                  # brick 6 is empty
    vc, cc = _cfgs()
    v, _ = distributed_vdi_step_mxu(
        mesh, _tf(), spec, vc, cc,
        bricks=bk.BrickMap(D, N, OWNER, lv))(sdata, origin, spacing, cam)
    _assert_vdi_close((v.color, v.depth), (even.color, even.depth))


@pytest.mark.parametrize("eye", [(0.0, 0.2, 4.0),    # march axis z
                                 (3.8, 0.3, 0.6)])   # march axis x
def test_mxu_coarse_smooth_field_psnr_floor(eye):
    """Uniform level-1 on a smooth field: the coarse march (pooled
    volume + dwm*2 + step_scale=1/2) holds a PSNR floor against the
    exact frame on both march axes. The committed bench ladder carries
    the quantitative claim; this guards the machinery."""
    data, origin, spacing = _smooth_scene()
    mesh = make_mesh(N)
    sdata = shard_volume(data, mesh)
    cam = _cam(eye)
    spec = _mxu_spec(cam)
    vc, cc = _cfgs(rebalance="even")
    even, _ = distributed_vdi_step_mxu(mesh, _tf(), spec, vc, cc)(
        sdata, origin, spacing, cam)
    vc, cc = _cfgs()
    v, _ = distributed_vdi_step_mxu(
        mesh, _tf(), spec, vc, cc,
        bricks=bk.BrickMap(D, N, tuple(range(N)), (1,) * 8))(
        sdata, origin, spacing, cam)
    from scenery_insitu_tpu.core.vdi import render_vdi_same_view

    fe = render_vdi_same_view(even)
    fl = render_vdi_same_view(v)
    psnr = _psnr(np.asarray(fe), np.asarray(fl))
    assert psnr > 28.0, psnr


def test_mxu_waves_zero_brick_rank_lod():
    """Satellite: a rank owning ZERO bricks runs end-to-end through the
    WAVES builder — with and without coarse levels — and matches the
    frame schedule."""
    data, origin, spacing = _scene()
    mesh = make_mesh(N)
    sdata = shard_volume(data, mesh)
    cam = _cam()
    spec = _mxu_spec(cam)
    for lv in (None, (0, 0, 0, 0, 0, 0, 2, 0)):
        bm = (bk.BrickMap(D, N, ISLANDS) if lv is None
              else bk.BrickMap(D, N, ISLANDS, lv))
        vc, cc = _cfgs()
        base, _ = distributed_vdi_step_mxu(mesh, _tf(), spec, vc, cc,
                                           bricks=bm)(
            sdata, origin, spacing, cam)
        vc, cc = _cfgs(schedule="waves", wave_tiles=2)
        w, _ = distributed_vdi_step_mxu(mesh, _tf(), spec, vc, cc,
                                        bricks=bm)(
            sdata, origin, spacing, cam)
        _assert_vdi_close((w.color, w.depth), (base.color, base.depth))


def test_gather_lod_map_renders_fine_and_ledgers():
    """The gather engine has no coarse march: a leveled map renders at
    level 0 (equal to the unleveled brick frame) and says so on the
    lod.engine ledger."""
    from scenery_insitu_tpu import obs

    data, origin, spacing = _scene()
    mesh = make_mesh(N)
    sdata = shard_volume(data, mesh)
    cam = _cam()
    obs.clear_ledger()
    vc, cc = _cfgs()
    base = distributed_vdi_step(mesh, _tf(), HW, HW, vc, cc, max_steps=48,
                                bricks=bk.BrickMap(D, N, OWNER))(
        sdata, origin, spacing, cam)
    vc, cc = _cfgs()
    v = distributed_vdi_step(
        mesh, _tf(), HW, HW, vc, cc, max_steps=48,
        bricks=bk.BrickMap(D, N, OWNER, (0, 0, 0, 0, 0, 0, 2, 0)))(
        sdata, origin, spacing, cam)
    np.testing.assert_array_equal(np.asarray(base.color),
                                  np.asarray(v.color))
    np.testing.assert_array_equal(np.asarray(base.depth),
                                  np.asarray(v.depth))
    assert any(e["component"] == "lod.engine" for e in obs.ledger())


# -------------------------------------------------------------- session


class _SkewedSim:
    kind = "skewed"

    def __init__(self):
        data = np.zeros((D, HW, HW), np.float32)
        data[1:8] = 0.6
        self._f = jnp.asarray(data)

    def advance(self, n):
        pass

    @property
    def field(self):
        return self._f


def _lod_session(**extra):
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.session import InSituSession

    cfg = FrameworkConfig().with_overrides(
        "composite.rebalance=bricks", "composite.rebalance_period=2",
        "composite.rebalance_bricks=8", "render.width=32",
        "render.height=32", "slicer.engine=mxu",
        "slicer.matmul_dtype=f32", "obs.enabled=true",
        "lod.enabled=true", "lod.error_px=1000", *extra.pop("over", []))
    return InSituSession(cfg, sim=_SkewedSim(), **extra)


def test_session_lod_replan_assigns_levels_and_renders():
    """e2e: lod.enabled + rebalance="bricks" — the replan fetches live
    + range profiles, assigns coarse levels to the empty bricks (the
    huge error_px admits coarsening everywhere the TF gate allows),
    recompiles keyed on the level tuple, and keeps rendering."""
    sess = _lod_session()
    out = None
    for _ in range(5):
        out = sess.render_frame()
    jax.block_until_ready(out)
    assert sess._bricks is not None
    assert max(sess._bricks.level) > 0
    # content bricks straddle the 0.05 ramp edge (range 0..0.6) -> fine
    assert sess._bricks.level[0] == 0
    ev = [e for e in sess.obs.events if e.get("name") == "rebalance_plan"]
    assert ev and max(ev[-1]["attrs"]["level"]) > 0


def test_session_lod_tf_straddle_after_steered_update():
    """Scenario-zoo path: a steered TF update moves the opacity edges;
    the very next replan re-runs the gate under the NEW TF (the update
    invalidates the plan clock), so bricks now straddling an edge are
    back at level 0 before the next marched frame."""
    sess = _lod_session()
    for _ in range(3):
        sess.render_frame()
    assert max(sess._bricks.level) > 0
    # new TF: opacity feature at 0.0..0.01 only — the 0.6 blobs go
    # transparent, their bricks' ranges [0, 0.6] straddle 0.01
    sess._apply_tf_message({
        "type": "tf",
        "points": [[0.0, 0.8], [0.01, 0.0], [1.0, 0.0]]})
    assert sess._plan_frame is None                # forced replan
    out = sess.render_frame()
    jax.block_until_ready(out)
    edges = opacity_edges(sess.tf)
    lo, hi = sess._replan_ranges()
    lo_b = lodm.per_brick(lo, sess._bricks.nbricks, "min")
    hi_b = lodm.per_brick(hi, sess._bricks.nbricks, "max")
    for i, lvl in enumerate(sess._bricks.level):
        straddles = any(lo_b[i] - 1e-4 < e < hi_b[i] + 1e-4
                        for e in edges)
        if straddles:
            assert lvl == 0, (i, lo_b[i], hi_b[i], edges)


def test_session_lod_inert_without_bricks_ledger():
    """lod.enabled without rebalance="bricks" has nothing to carry
    levels — the knob ledgers inert instead of silently rendering
    level 0."""
    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.session import InSituSession

    obs.clear_ledger()
    cfg = FrameworkConfig().with_overrides(
        "lod.enabled=true", "render.width=32", "render.height=32",
        "slicer.engine=mxu", "slicer.matmul_dtype=f32")
    sess = InSituSession(cfg, sim=_SkewedSim())
    jax.block_until_ready(sess.render_frame())
    assert any(e["component"] == "lod.inert" for e in obs.ledger())
    assert sess._bricks is None
