"""Non-convex brick partitions (CompositeConfig.rebalance == "bricks";
docs/SCENARIOS.md "Brick maps"): BrickMap / steal_plan units, the
reslab_bricks shuffle, adversarial property tests of the composite
primitives the brick path leans on (merge_vdis_pairwise /
resegment_stream under interleaved non-convex inputs), and the
correctness keystone — COMPOSITE INVARIANCE: permuting brick ownership
leaves the composited frame unchanged on the 8-device virtual mesh.

Parity gates, and why each is what it is:
- gather VDI step: BITWISE between ownership permutations. Every
  brick's fragment is generated against the brick's clip AABB on the
  GLOBAL sample ladder — identical whichever rank marched it — and the
  composite's per-pixel stable sort canonicalizes the stacked order.
- mxu steps (both march regimes, waves + ring crosses, temporal): 1e-5
  (the PR-6 fusion-noise gate for separately-compiled programs; on the
  power-of-two-spacing scene the diffs measure 0.0).
- bricks vs the plain even split: same gates — the scene keeps content
  >= 2 slices clear of every brick AND slab boundary and under the
  per-region K budget, so segment structure coincides (the PR-10
  K-truncation caveat applies to bricks identically).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scenery_insitu_tpu.config import (CompositeConfig, SliceMarchConfig,
                                       VDIConfig)
from scenery_insitu_tpu.core.camera import Camera
from scenery_insitu_tpu.core.transfer import TransferFunction
from scenery_insitu_tpu.ops.composite import (merge_vdis_pairwise,
                                              resegment_stream,
                                              sort_stream)
from scenery_insitu_tpu.parallel import bricks as bk
from scenery_insitu_tpu.parallel.mesh import make_mesh, reslab_bricks
from scenery_insitu_tpu.parallel.pipeline import (_resolve_bricks,
                                                  distributed_vdi_step,
                                                  distributed_vdi_step_mxu,
                                                  shard_volume)
from jax import shard_map

N = 8
D = 32
HW = 16
ATOL = 1e-5

# single-brick-per-rank non-convex assignment + an ownership relabeling
OWNER = (3, 0, 5, 1, 4, 7, 2, 6)
PERM = (2, 0, 3, 1, 5, 7, 4, 6)
# two disjoint interleaved slabs per rank (B = 2)
INTERLEAVED = tuple(list(range(N)) + list(range(N)))
# ownership islands + an empty rank (rank 7 owns nothing)
ISLANDS = (0, 0, 1, 2, 3, 4, 5, 6)


def _cam(eye=(0.0, 0.2, 4.0)):
    return Camera.create(eye, fov_y_deg=50.0, near=0.5, far=20.0)


def _tf():
    return TransferFunction.ramp(0.05, 0.8, 0.7)


def _scene():
    """Smooth constant-value blobs >= 2 slices clear of every brick
    boundary (bz=4 and bz=2 grids) and of the even split, power-of-two
    voxel spacing — the same construction as tests/test_rebalance.py."""
    data = np.zeros((D, HW, HW), np.float32)
    blobs = [(1, 3, 0.3), (5, 7, 0.5), (9, 11, 0.7), (13, 15, 0.4),
             (17, 19, 0.6), (21, 23, 0.8), (29, 31, 0.45)]
    for a, b, v in blobs:
        data[a:b] = v
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


# ---------------------------------------------------------- BrickMap units


def test_brickmap_validation():
    with pytest.raises(ValueError, match="divide"):
        bk.BrickMap(30, 4, (0, 1, 2, 3, 0, 1, 2))       # 7 bricks / 30
    with pytest.raises(ValueError, match="outside"):
        bk.BrickMap(32, 4, (0, 1, 2, 4))
    with pytest.raises(ValueError, match="permutation"):
        bk.BrickMap(32, 4, (0, 1, 2, 3)).permute([0, 0, 1, 2])
    with pytest.raises(ValueError, match="n_ranks"):
        bk.BrickMap.even(32, 3, nbricks=4)


def test_brickmap_geometry_and_tables():
    bm = bk.BrickMap(D, N, ISLANDS)
    assert bm.nbricks == 8 and bm.brick_depth == 4
    assert bm.slots == 2
    assert bm.rank_bricks(0) == (0, 1)
    assert bm.rank_bricks(7) == ()
    table = bm.start_table()
    assert table.shape == (N, 2)
    assert list(table[0]) == [0, 4]
    assert list(table[7]) == [-1, -1]
    assert bm.intervals(1) == [(8, 12)]


def test_brickmap_even_convex_detection():
    assert bk.BrickMap.even(D, N).is_even_convex()
    assert bk.BrickMap.even(D, N, nbricks=16).is_even_convex()
    assert bk.BrickMap.contiguous(D, N, 16).is_even_convex()
    assert not bk.BrickMap(D, N, OWNER).is_even_convex()
    # contiguous with a non-dividing brick count is a valid seed but
    # not the even map
    assert not bk.BrickMap.contiguous(30 * N, N, 30).is_even_convex()


def test_auto_nbricks_divides():
    for d, n in [(96, 8), (100, 8), (32, 8), (512, 8), (7, 2)]:
        nb = bk.auto_nbricks(d, n)
        assert d % nb == 0
        assert nb <= max(n, 4 * n)


def test_brick_work_and_straggler():
    prof = np.zeros(16)
    prof[:4] = 1.0                       # live work in the low quarter
    work = bk.brick_work(prof, D, 16, base_cost=0.0)
    assert work[:4].sum() > 0 and work[4:].sum() == 0
    even = bk.BrickMap.even(D, N, nbricks=16)
    assert bk.straggler_factor(even, work) > 2.0


def test_steal_plan_equalizes_and_caps_moves():
    prof = np.zeros(16)
    prof[:4] = 1.0
    work = bk.brick_work(prof, D, 16)
    even = bk.BrickMap.even(D, N, nbricks=16)
    s0 = bk.straggler_factor(even, work)
    bm = bk.steal_plan(even, work, max_moves=2, hysteresis=0.0)
    # the move cap binds per replan; iterating replans converges
    assert sum(a != b for a, b in zip(bm.owner, even.owner)) <= 2
    assert bk.straggler_factor(bm, work) < s0
    for _ in range(8):
        bm = bk.steal_plan(bm, work, max_moves=2, hysteresis=0.0)
    assert bk.straggler_factor(bm, work) < s0 / 1.5


def test_steal_plan_hysteresis_object_equal():
    work = np.ones(16)                   # perfectly balanced already
    even = bk.BrickMap.even(D, N, nbricks=16)
    assert bk.steal_plan(even, work, hysteresis=0.1) is even
    # and a converged skewed plan stays put
    prof = np.zeros(16)
    prof[:4] = 1.0
    w = bk.brick_work(prof, D, 16)
    bm = even
    for _ in range(10):
        bm = bk.steal_plan(bm, w, max_moves=2, hysteresis=0.1)
    assert bk.steal_plan(bm, w, max_moves=2, hysteresis=0.1) is bm


# ------------------------------------------------------- reslab_bricks


def test_reslab_bricks_contents_halo_and_absent_slots():
    mesh = make_mesh(N)
    data = np.arange(D * 4 * 4, dtype=np.float32).reshape(D, 4, 4)
    sdata = shard_volume(jnp.asarray(data), mesh)
    bm = bk.BrickMap(D, N, ISLANDS)
    from jax.sharding import PartitionSpec as P

    f = jax.jit(shard_map(
        lambda x: reslab_bricks(x, bm, "ranks", h=1), mesh=mesh,
        in_specs=P("ranks", None, None),
        out_specs=P("ranks", None, None, None), check_vma=False))
    out = np.asarray(f(sdata)).reshape(N, bm.slots, bm.brick_depth + 2,
                                       4, 4)
    table = bm.start_table()
    for r in range(N):
        for s in range(bm.slots):
            st = table[r, s]
            if st < 0:
                assert (out[r, s] == 0).all()
                continue
            rows = np.clip(np.arange(st - 1, st + bm.brick_depth + 1),
                           0, D - 1)
            np.testing.assert_array_equal(out[r, s], data[rows])


def test_reslab_bricks_rejects_mismatched_geometry():
    mesh = make_mesh(N)
    data = shard_volume(jnp.zeros((D, 4, 4)), mesh)
    from jax.sharding import PartitionSpec as P

    for bm, msg in ((bk.BrickMap(D, 4, (0, 1, 2, 3)), "ranks"),
                    (bk.BrickMap(2 * D, N, tuple(range(N))), "depth")):
        with pytest.raises(ValueError, match=msg):
            jax.jit(shard_map(
                lambda x, bm=bm: reslab_bricks(x, bm, "ranks"),
                mesh=mesh, in_specs=P("ranks", None, None),
                out_specs=P("ranks", None, None, None),
                check_vma=False))(data)


# ------------------------- adversarial merge / resegment property tests


def _random_sorted_stream(rng, k, h, w, n_live, lo=0.0, hi=1.0):
    """Per-pixel depth-sorted, empty-masked stream with ``n_live`` live
    slots drawn from disjoint sub-intervals of [lo, hi) — the shape a
    brick fragment has after sort_stream."""
    starts = np.full((k, h, w), np.inf, np.float32)
    ends = np.full((k, h, w), np.inf, np.float32)
    colors = np.zeros((k, 4, h, w), np.float32)
    if n_live:
        edges = np.sort(rng.uniform(lo, hi, size=(2 * n_live, h, w)),
                        axis=0)
        starts[:n_live] = edges[0::2]
        ends[:n_live] = edges[1::2]
        a = rng.uniform(0.05, 0.9, size=(n_live, h, w)).astype(np.float32)
        rgb = rng.uniform(0.0, 1.0, size=(n_live, 3, h, w)) * a[:, None]
        colors[:n_live, :3] = rgb
        colors[:n_live, 3] = a
    depth = np.stack([starts, ends], axis=1).astype(np.float32)
    return jnp.asarray(colors), jnp.asarray(depth)


def _merge_reference(ca, da, cb, db):
    """Stable concat + argsort-by-start — the sorted-reference merge."""
    c = jnp.concatenate([ca, cb], axis=0)
    d = jnp.concatenate([da, db], axis=0)
    return sort_stream(c, d)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_merge_pairwise_interleaved_matches_sorted_reference(seed):
    """Two ranks owning interleaved disjoint depth ranges (the
    non-convex case): the pairwise merge equals the sorted reference,
    payloads bit-for-bit (+inf empties included)."""
    rng = np.random.default_rng(seed)
    # stream a in even-indexed bands, stream b in odd — interleaved
    ca, da = _random_sorted_stream(rng, 6, 3, 4, 4, lo=0.0, hi=1.0)
    cb, db = _random_sorted_stream(rng, 6, 3, 4, 3, lo=0.05, hi=1.05)
    mc, md = merge_vdis_pairwise(ca, da, cb, db)
    rc, rd = _merge_reference(ca, da, cb, db)
    np.testing.assert_array_equal(np.asarray(mc), np.asarray(rc))
    np.testing.assert_array_equal(np.asarray(md), np.asarray(rd))


def test_merge_pairwise_empty_brick_ranks():
    """An empty-brick rank (all +inf) merges as the identity on the
    other stream; two empties merge to all-empty."""
    rng = np.random.default_rng(3)
    ca, da = _random_sorted_stream(rng, 5, 2, 3, 4)
    ce, de = _random_sorted_stream(rng, 5, 2, 3, 0)
    mc, md = merge_vdis_pairwise(ca, da, ce, de)
    np.testing.assert_array_equal(np.asarray(mc[:5]), np.asarray(ca))
    np.testing.assert_array_equal(np.asarray(md[:5]), np.asarray(da))
    assert np.isinf(np.asarray(md[5:, 0])).all()
    mc2, md2 = merge_vdis_pairwise(ce, de, ce, de)
    assert np.isinf(np.asarray(md2[:, 0])).all()
    assert (np.asarray(mc2) == 0).all()


def test_merge_truncation_radiance_monotone():
    """K-truncation keeps the NEAREST k_cap segments: retained radiance
    (summed premultiplied energy of kept live slots) is monotone
    non-decreasing in k_cap, and the kept prefix is bit-stable."""
    rng = np.random.default_rng(4)
    ca, da = _random_sorted_stream(rng, 8, 3, 3, 6)
    cb, db = _random_sorted_stream(rng, 8, 3, 3, 6, lo=0.02, hi=1.02)
    prev_rad = -1.0
    prev = None
    for cap in (8, 10, 12, 16):
        mc, md = merge_vdis_pairwise(ca, da, cb, db, k_cap=cap)
        live = np.isfinite(np.asarray(md[:, 0]))
        rad = float(np.sum(np.asarray(mc) * live[:, None]))
        assert rad >= prev_rad - 1e-6
        if prev is not None:
            np.testing.assert_array_equal(np.asarray(mc)[:prev.shape[0]],
                                          prev)
        prev_rad = rad
        prev = np.asarray(mc)


@pytest.mark.parametrize("seed", [0, 5])
def test_resegment_invariant_to_empty_slot_padding(seed):
    """The brick-path invariant: a sorted stream and the same stream
    with extra +inf empty slots appended (what padded brick slots
    contribute) re-segment IDENTICALLY — slot count is shape, not
    content."""
    rng = np.random.default_rng(seed)
    sc, sd = _random_sorted_stream(rng, 6, 3, 4, 5)
    pad_c = jnp.zeros((4,) + tuple(sc.shape[1:]), jnp.float32)
    pad_d = jnp.full((4,) + tuple(sd.shape[1:]), jnp.inf, jnp.float32)
    cfg = CompositeConfig(max_output_supersegments=5, adaptive_iters=3,
                          backend="xla")
    a = resegment_stream(sc, sd, cfg)
    b = resegment_stream(jnp.concatenate([sc, pad_c]),
                         jnp.concatenate([sd, pad_d]), cfg)
    np.testing.assert_array_equal(np.asarray(a.color), np.asarray(b.color))
    np.testing.assert_array_equal(np.asarray(a.depth), np.asarray(b.depth))


# --------------------------------------------- composite invariance matrix


def _assert_vdi_close(a, b, atol=ATOL):
    ac, ad = np.asarray(a[0]), np.asarray(a[1])
    bc, bd = np.asarray(b[0]), np.asarray(b[1])
    np.testing.assert_allclose(ac, bc, atol=atol, rtol=0)
    assert (np.isinf(ad) == np.isinf(bd)).all()
    fin = np.isfinite(ad)
    np.testing.assert_allclose(ad[fin], bd[fin], atol=atol, rtol=0)


def test_gather_brick_permutation_bitwise():
    """The keystone: permuting brick ownership leaves the gather
    builder's composited frame BITWISE unchanged, and the brick frame
    matches the even decomposition."""
    data, origin, spacing = _scene()
    mesh = make_mesh(N)
    sdata = shard_volume(data, mesh)
    bm = bk.BrickMap(D, N, OWNER)
    outs = []
    for b in (bm, bm.permute(PERM)):
        vc, cc = _cfgs()
        step = distributed_vdi_step(mesh, _tf(), HW, HW, vc, cc,
                                    max_steps=48, bricks=b)
        v = step(sdata, origin, spacing, _cam())
        outs.append((np.asarray(v.color), np.asarray(v.depth)))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_array_equal(outs[0][1], outs[1][1])
    vc, cc = _cfgs(rebalance="even")
    even = distributed_vdi_step(mesh, _tf(), HW, HW, vc, cc,
                                max_steps=48)(sdata, origin, spacing,
                                              _cam())
    _assert_vdi_close(outs[0], (even.color, even.depth))


@pytest.mark.parametrize("eye", [(0.0, 0.2, 4.0),    # march axis z
                                 (3.8, 0.3, 0.6)])   # march axis x
def test_mxu_brick_permutation_matches_even(eye):
    """MXU engine, both march regimes: ownership permutations agree and
    the brick frame equals the even frame at the 1e-5 gate (z bricks own
    marched slices through w_bounds, x/y bricks through v_bounds)."""
    data, origin, spacing = _scene()
    mesh = make_mesh(N)
    sdata = shard_volume(data, mesh)
    cam = _cam(eye)
    spec = _mxu_spec(cam)
    bm = bk.BrickMap(D, N, OWNER)
    outs = []
    for b in (bm, bm.permute(PERM)):
        vc, cc = _cfgs()
        v, meta = distributed_vdi_step_mxu(mesh, _tf(), spec, vc, cc,
                                           bricks=b)(
            sdata, origin, spacing, cam)
        outs.append((v.color, v.depth, np.asarray(meta.volume_dims)))
    _assert_vdi_close(outs[0][:2], outs[1][:2])
    # metadata keeps describing the GLOBAL volume
    np.testing.assert_array_equal(outs[0][2],
                                  np.asarray([HW, HW, D], np.float32))
    vc, cc = _cfgs(rebalance="even")
    even, _ = distributed_vdi_step_mxu(mesh, _tf(), spec, vc, cc)(
        sdata, origin, spacing, cam)
    _assert_vdi_close(outs[0][:2], (even.color, even.depth))


def test_mxu_interleaved_and_empty_rank_maps_match_even():
    """Adversarial maps: two interleaved disjoint slabs per rank (B=2)
    and ownership islands with an empty rank — all equal the even
    frame."""
    data, origin, spacing = _scene()
    mesh = make_mesh(N)
    sdata = shard_volume(data, mesh)
    cam = _cam()
    spec = _mxu_spec(cam)
    vc, cc = _cfgs(rebalance="even")
    even, _ = distributed_vdi_step_mxu(mesh, _tf(), spec, vc, cc)(
        sdata, origin, spacing, cam)
    for owner in (INTERLEAVED, ISLANDS):
        vc, cc = _cfgs()
        v, _ = distributed_vdi_step_mxu(
            mesh, _tf(), spec, vc, cc,
            bricks=bk.BrickMap(D, N, owner))(sdata, origin, spacing, cam)
        _assert_vdi_close((v.color, v.depth), (even.color, even.depth))


def test_mxu_brick_waves_and_ring_cross_match_frame():
    """Waves x bricks and ring x bricks: the tile-wave overlap pipeline
    and the pairwise-merge ring both reproduce the brick frame
    schedule's all_to_all output."""
    data, origin, spacing = _scene()
    mesh = make_mesh(N)
    sdata = shard_volume(data, mesh)
    cam = _cam()
    spec = _mxu_spec(cam)
    bm = bk.BrickMap(D, N, OWNER)
    vc, cc = _cfgs()
    base, _ = distributed_vdi_step_mxu(mesh, _tf(), spec, vc, cc,
                                       bricks=bm)(
        sdata, origin, spacing, cam)
    for kw in (dict(schedule="waves", wave_tiles=2),
               dict(exchange="ring")):
        vc, cc = _cfgs(**kw)
        v, _ = distributed_vdi_step_mxu(mesh, _tf(), spec, vc, cc,
                                        bricks=bm)(
            sdata, origin, spacing, cam)
        _assert_vdi_close((v.color, v.depth), (base.color, base.depth))


def test_mxu_brick_temporal_carry_matches_even():
    """Temporal mode: per-slot threshold maps (row-stacked carry) over 3
    frames match the even decomposition's frames."""
    from scenery_insitu_tpu.parallel.pipeline import (
        distributed_initial_threshold_mxu, distributed_vdi_step_mxu_temporal)

    data, origin, spacing = _scene()
    mesh = make_mesh(N)
    sdata = shard_volume(data, mesh)
    cam = _cam()
    spec = _mxu_spec(cam)
    cfg_t = VDIConfig(max_supersegments=6, adaptive_mode="temporal")
    bm = bk.BrickMap(D, N, OWNER)
    runs = {}
    for b in (None, bm):
        cc = CompositeConfig(max_output_supersegments=12, adaptive_iters=2,
                             rebalance="bricks" if b else "even")
        thr = distributed_initial_threshold_mxu(
            mesh, _tf(), spec, cfg_t, bricks=b)(sdata, origin, spacing,
                                                cam)
        step = distributed_vdi_step_mxu_temporal(mesh, _tf(), spec, cfg_t,
                                                 cc, bricks=b)
        frames = []
        for _ in range(3):
            (v, _), thr = step(sdata, origin, spacing, cam, thr)
            frames.append((np.asarray(v.color), np.asarray(v.depth)))
        runs[b is not None] = frames
    for fr_b, fr_e in zip(runs[True], runs[False]):
        _assert_vdi_close(fr_b, fr_e)


# --------------------------------------------- resolution + observability


def test_even_convex_map_short_circuits():
    """The even-convex map resolves to None — builders take the
    pre-brick path bitwise, and no brick build markers mint."""
    from scenery_insitu_tpu import obs

    rec = obs.Recorder(enabled=True)
    prev = obs.set_recorder(rec)
    try:
        cc = CompositeConfig(rebalance="bricks")
        assert _resolve_bricks(cc, N, bk.BrickMap.even(D, N)) is None
        assert _resolve_bricks(cc, N, bk.BrickMap.even(D, N, 16)) is None
        assert _resolve_bricks(cc, 1, bk.BrickMap(D, 1, (0,))) is None
    finally:
        obs.set_recorder(prev)
    assert rec.counters.get("bricks_steps_built", 0) == 0


def test_resolve_bricks_validation():
    bm = bk.BrickMap(D, N, OWNER)
    with pytest.raises(ValueError, match="rebalance"):
        _resolve_bricks(CompositeConfig(), N, bm)
    with pytest.raises(ValueError, match="ranks"):
        _resolve_bricks(CompositeConfig(rebalance="bricks"), 4, bm)
    with pytest.raises(TypeError):
        _resolve_bricks(CompositeConfig(rebalance="bricks"), N, (0, 1))


def test_brick_build_emits_obs_counters():
    from scenery_insitu_tpu import obs

    data, origin, spacing = _scene()
    rec = obs.Recorder(enabled=True)
    prev = obs.set_recorder(rec)
    try:
        mesh = make_mesh(N)
        vc, cc = _cfgs()
        bm = bk.BrickMap(D, N, ISLANDS)
        step = distributed_vdi_step_mxu(mesh, _tf(), _mxu_spec(_cam()),
                                        vc, cc, bricks=bm)
        step(shard_volume(data, mesh), origin, spacing, _cam())
    finally:
        obs.set_recorder(prev)
    assert rec.counters.get("bricks_steps_built", 0) >= 1
    builds = [e for e in rec.events if e.get("name") == "bricks_build"]
    assert builds and builds[0]["attrs"]["owner"] == list(ISLANDS)
    assert builds[0]["attrs"]["slots"] == 2
    assert builds[0]["attrs"]["bricks_per_rank"][7] == 0


def test_bricks_inert_builders_ledger():
    """Hybrid/plain builders have no brick march — a configured map
    lands on the bricks.partition ledger, not a silent even render."""
    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.parallel.pipeline import (
        distributed_hybrid_step_mxu, distributed_plain_step)

    obs.clear_ledger()
    mesh = make_mesh(N)
    bm = bk.BrickMap(D, N, OWNER)
    vc, cc = _cfgs()
    distributed_hybrid_step_mxu(mesh, _tf(), _mxu_spec(_cam()), vc, cc,
                                bricks=bm)
    distributed_plain_step(mesh, _tf(), HW, HW,
                           comp_cfg=CompositeConfig(rebalance="bricks"),
                           bricks=bm)
    rows = [e for e in obs.ledger()
            if e["component"] == "bricks.partition"]
    assert len(rows) >= 2


# -------------------------------------------------------------- session


class _SkewedSim:
    """Static skewed field (content low-z only) for session replans."""

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


def test_session_brick_replan_rebuilds_and_balances():
    """rebalance="bricks" e2e: the session fetches the live profile,
    steals bricks off the loaded ranks (move-capped), recompiles, and
    keeps rendering — the adopted map reduces the modeled straggler."""
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.session import InSituSession

    cfg = FrameworkConfig().with_overrides(
        "composite.rebalance=bricks", "composite.rebalance_period=2",
        "composite.rebalance_bricks=16", "render.width=32",
        "render.height=32", "slicer.engine=mxu",
        "slicer.matmul_dtype=f32", "obs.enabled=true")
    sess = InSituSession(cfg, sim=_SkewedSim())
    out = None
    for _ in range(5):
        out = sess.render_frame()
    jax.block_until_ready(out)
    assert sess.obs.counters.get("rebalance_replans", 0) >= 1
    assert sess.obs.counters.get("bricks_steps_built", 0) >= 1
    assert sess._bricks is not None and not sess._bricks.is_even_convex()
    ev = [e for e in sess.obs.events if e.get("name") == "rebalance_plan"]
    assert ev and ev[0]["attrs"]["kind"] == "bricks"
    assert ev[0]["attrs"]["straggler_planned"] \
        < ev[0]["attrs"]["straggler_even"]


def test_session_rejects_non_dividing_brick_count():
    """Impossible brick geometry fails at session build, naming the
    knob — not minutes into a run at the first replan."""
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.session import InSituSession

    cfg = FrameworkConfig().with_overrides(
        "composite.rebalance=bricks", "composite.rebalance_bricks=10",
        "sim.grid=[32,16,16]", "render.width=32", "render.height=32")
    with pytest.raises(ValueError, match="rebalance_bricks"):
        InSituSession(cfg)


def test_session_brick_replan_inert_off_vdi_mode():
    """Modes whose builders ledger the brick map inert (plain/hybrid)
    must not replan at all — an adopted map would recompile steps that
    render even slabs regardless."""
    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.session import InSituSession

    obs.clear_ledger()
    cfg = FrameworkConfig().with_overrides(
        "composite.rebalance=bricks", "composite.rebalance_period=1",
        "runtime.generate_vdis=false", "slicer.engine=gather",
        "render.width=32", "render.height=32", "render.max_steps=32",
        "sim.grid=[16,16,16]", "sim.steps_per_frame=1",
        "obs.enabled=true")
    sess = InSituSession(cfg)
    assert sess.mode == "plain"
    for _ in range(2):
        out = sess.render_frame()
    jax.block_until_ready(out)
    assert sess.obs.counters.get("rebalance_replans", 0) == 0
    assert sess._bricks is None
    assert any(e["component"] == "bricks.partition"
               for e in obs.ledger())


def test_session_brick_replan_inert_on_single_rank():
    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.session import InSituSession

    obs.clear_ledger()
    cfg = FrameworkConfig().with_overrides(
        "composite.rebalance=bricks", "mesh.num_devices=1",
        "render.width=32", "render.height=32", "slicer.engine=gather",
        "sim.grid=[16,16,16]", "sim.steps_per_frame=1")
    sess = InSituSession(cfg)
    jax.block_until_ready(sess.render_frame())
    assert any(e["component"] == "occupancy.rebalance"
               for e in obs.ledger())
    assert sess._bricks is None
