"""Quantized supersegment wire formats for the sort-last exchange
(CompositeConfig.wire = "f32" | "bf16" | "qpack8"; ops/wire.py,
docs/PERF.md "Wire formats"): encode/decode round-trip units (empty-slot
sentinel, near==far fragments, tie depths), PSNR floors for every
distributed builder × both exchange modes on the 8-device virtual mesh,
obs counter assertions, the traffic-model numbers, and the host-side
quantizer reuse (io.vdi_io / runtime.streaming)."""

import jax.numpy as jnp
import numpy as np
import pytest

from scenery_insitu_tpu.config import (CompositeConfig, RenderConfig,
                                       SliceMarchConfig, VDIConfig)
from scenery_insitu_tpu.core.camera import Camera
from scenery_insitu_tpu.core.transfer import TransferFunction
from scenery_insitu_tpu.core.vdi import VDI, VDIMetadata, render_vdi_same_view
from scenery_insitu_tpu.core.volume import procedural_volume
from scenery_insitu_tpu.ops import wire as wire_mod
from scenery_insitu_tpu.ops.composite import modeled_exchange_traffic
from scenery_insitu_tpu.parallel.mesh import make_mesh
from scenery_insitu_tpu.parallel.pipeline import (distributed_plain_step,
                                                  distributed_vdi_step,
                                                  shard_volume)
from scenery_insitu_tpu.utils.image import psnr

W = H = 16
STEPS = 48
N = 8
LOSSY = ("bf16", "qpack8")
EXCHANGES = ("all_to_all", "ring")
# the documented floor (docs/PERF.md "Wire formats") on the 8-device
# parity scenes; measured headroom is ~60 dB (qpack8) / ~75 dB (bf16)
PSNR_FLOOR = 40.0


def _cam(eye=(0.0, 0.2, 4.0)):
    return Camera.create(eye, fov_y_deg=50.0, near=0.5, far=20.0)


def _tf():
    return TransferFunction.ramp(0.05, 0.8, 0.7)


def _stream(rng, k, h, w, live, lo=1.0, hi=5.0, ext=(0.01, 0.2)):
    """Random per-pixel depth-sorted segment stream with ``live`` live
    slots (empties masked: zero color, +inf depth). ``ext`` bounds the
    segment extents — the round-trip unit tests keep the near-degenerate
    default, quality-floor tests pick extents that are wide relative to
    the fragment's depth span (sub-quantum-thin translucent segments are
    outside the documented floor contract; the unit tests bound their
    error exactly instead)."""
    s = np.sort(rng.uniform(lo, hi, (k, h, w)), axis=0).astype(np.float32)
    e = (s + rng.uniform(*ext, (k, h, w))).astype(np.float32)
    c = rng.uniform(0.0, 1.0, (k, 4, h, w)).astype(np.float32)
    mask = np.arange(k)[:, None, None] < live
    s = np.where(mask, s, np.inf)
    e = np.where(mask, e, np.inf)
    c = np.where(mask[:, None], c, 0.0)
    return jnp.asarray(c), jnp.asarray(np.stack([s, e], axis=1))


def _render(color, depth):
    return np.asarray(render_vdi_same_view(VDI(color, depth)))


# ------------------------------------------------------ encode/decode units

def test_f32_encode_is_identity():
    """The f32 wire inserts NOTHING: the very arrays go through."""
    rng = np.random.default_rng(0)
    c, d = _stream(rng, 4, 3, 5, live=2)
    ec, ed, sc = wire_mod.encode_fragment(c, d, "f32")
    assert ec is c and ed is d and sc is None
    dc, dd = wire_mod.decode_fragment(ec, ed, None, "f32")
    assert dc is c and dd is d


@pytest.mark.parametrize("wire", LOSSY)
def test_lossy_roundtrip_preserves_empty_sentinel(wire):
    """+inf empty slots round-trip EXACTLY (bf16 keeps inf; qpack8
    reserves the u16 sentinel 0xFFFF) and their colors stay zero — the
    merge/re-segmentation empty-slot convention is untouched."""
    rng = np.random.default_rng(1)
    c, d = _stream(rng, 6, 4, 4, live=3)
    ec, ed, sc = wire_mod.encode_fragment(c, d, wire)
    dc, dd = wire_mod.decode_fragment(ec, ed, sc, wire)
    dc, dd = np.asarray(dc), np.asarray(dd)
    np.testing.assert_array_equal(np.isinf(dd), np.isinf(np.asarray(d)))
    assert (dc[3:] == 0.0).all()
    assert np.isfinite(dd[:3]).all()


def test_qpack8_error_bounds():
    """|decoded - original| is bounded by one quantum: fragment depth
    span / 254 for depths, 1/255 for colors (half-quantum after round)."""
    rng = np.random.default_rng(2)
    c, d = _stream(rng, 8, 6, 6, live=8)
    ec, ed, sc = wire_mod.encode_fragment(c, d, "qpack8")
    dc, dd = wire_mod.decode_fragment(ec, ed, sc, "qpack8")
    dn, df = np.asarray(d), np.asarray(dd)
    span = dn[np.isfinite(dn)].max() - dn[np.isfinite(dn)].min()
    assert np.abs(np.asarray(dc) - np.asarray(c)).max() <= 0.5 / 255 + 1e-6
    assert np.abs(df - dn).max() <= 0.5 * span / 254 + 1e-5


def test_qpack8_fully_empty_fragment():
    """A fragment with NO finite depth encodes to all-sentinel and
    decodes to all +inf / zero color — no NaNs from the degenerate
    [near, far]."""
    c = jnp.zeros((3, 4, 2, 2), jnp.float32)
    d = jnp.full((3, 2, 2, 2), jnp.inf, jnp.float32)
    ec, ed, sc = wire_mod.encode_fragment(c, d, "qpack8")
    assert (np.asarray(ed) == 0xFFFF).all()
    dc, dd = wire_mod.decode_fragment(ec, ed, sc, "qpack8")
    assert np.isinf(np.asarray(dd)).all()
    assert (np.asarray(dc) == 0.0).all()


def test_qpack8_near_equals_far_fragment():
    """All live depths identical (span 0): codes collapse to 0 and decode
    EXACTLY to that depth (near + 0·span)."""
    rng = np.random.default_rng(3)
    c, d = _stream(rng, 4, 3, 3, live=2)
    d = jnp.where(jnp.isfinite(d), jnp.float32(2.5), jnp.inf)
    ec, ed, sc = wire_mod.encode_fragment(c, d, "qpack8")
    dc, dd = wire_mod.decode_fragment(ec, ed, sc, "qpack8")
    fin = np.isfinite(np.asarray(d))
    assert (np.asarray(dd)[fin] == 2.5).all()
    np.testing.assert_array_equal(np.isinf(np.asarray(dd)), ~fin)


@pytest.mark.parametrize("wire", LOSSY)
def test_lossy_roundtrip_preserves_sort_and_ties(wire):
    """Quantization is monotone: a per-pixel depth-sorted stream decodes
    sorted (the ring pairwise-merge precondition), and exactly-equal
    start depths stay exactly equal (tie structure survives)."""
    rng = np.random.default_rng(4)
    c, d = _stream(rng, 8, 4, 4, live=6)
    d = np.array(d)                         # writable host copy
    d[3, 0] = d[2, 0]                       # manufacture a tie
    ec, ed, sc = wire_mod.encode_fragment(jnp.asarray(c), jnp.asarray(d),
                                          wire)
    _, dd = wire_mod.decode_fragment(ec, ed, sc, wire)
    starts = np.asarray(dd)[:, 0]
    assert (np.sort(starts, axis=0) == starts).all()
    np.testing.assert_array_equal(starts[3], starts[2])


def test_qpack8_np_matches_device_encode():
    """The numpy twin (the vdi_io / VDIPublisher pre-codec pass) produces
    bit-identical codes to the device encode — one format, two hosts."""
    rng = np.random.default_rng(5)
    c, d = _stream(rng, 6, 5, 7, live=4)
    ec, ed, sc = wire_mod.encode_fragment(c, d, "qpack8")
    nc, nd, near, far = wire_mod.qpack8_quantize_np(np.asarray(c),
                                                    np.asarray(d))
    np.testing.assert_array_equal(nc, np.asarray(ec))
    np.testing.assert_array_equal(nd, np.asarray(ed))
    assert np.float32(near) == float(sc[0])
    assert np.float32(far) == float(sc[1])
    bc, bd = wire_mod.qpack8_dequantize_np(nc, nd, near, far)
    dc, dd = wire_mod.decode_fragment(ec, ed, sc, "qpack8")
    np.testing.assert_allclose(bc, np.asarray(dc), atol=1e-7, rtol=0)
    fin = np.isfinite(bd)
    np.testing.assert_array_equal(fin, np.isfinite(np.asarray(dd)))
    np.testing.assert_allclose(bd[fin], np.asarray(dd)[fin], atol=1e-5,
                               rtol=0)


def test_plain_roundtrip():
    """Plain fragments (single depth per pixel): qpack8 gives the lone
    depth the full u16 range; the 0xFFFF sentinel round-trips +inf."""
    rng = np.random.default_rng(6)
    img = rng.uniform(0, 1, (4, 6, 8)).astype(np.float32)
    dep = rng.uniform(1, 5, (6, 8)).astype(np.float32)
    dep[0, 0] = np.inf
    for wire in LOSSY:
        ei, ed, sc = wire_mod.encode_plain(jnp.asarray(img),
                                           jnp.asarray(dep), wire)
        di, dd = wire_mod.decode_plain(ei, ed, sc, wire)
        dd = np.asarray(dd)
        np.testing.assert_array_equal(np.isinf(dd), np.isinf(dep))
        fin = np.isfinite(dep)
        span = dep[fin].max() - dep[fin].min()
        tol = (span / 65534 if wire == "qpack8" else 0.02 * dep[fin].max())
        assert np.abs(dd[fin] - dep[fin]).max() <= tol + 1e-6


def test_wire_validation():
    with pytest.raises(ValueError, match="wire"):
        CompositeConfig(wire="u4")
    with pytest.raises(ValueError, match="wire"):
        wire_mod.wire_slot_bytes("u4")
    with pytest.raises(ValueError, match="wire"):
        wire_mod.encode_fragment(jnp.zeros((1, 4, 1, 1)),
                                 jnp.zeros((1, 2, 1, 1)), "u4")


# ------------------------------------------------------------ traffic model

def test_modeled_traffic_per_wire_itemsizes():
    """The model matches what ships: qpack8 cuts ici_bytes_per_rank 4×
    (24 → 6 B/slot), bf16 2×; HBM stream bytes are wire-independent
    (decode to f32 precedes the fold)."""
    f32 = modeled_exchange_traffic(8, 16, 720, 1280, k_out=16)
    bf = modeled_exchange_traffic(8, 16, 720, 1280, k_out=16, wire="bf16")
    q8 = modeled_exchange_traffic(8, 16, 720, 1280, k_out=16,
                                  wire="qpack8")
    assert f32["wire_color_bytes_per_slot"] == 16
    assert f32["wire_depth_bytes_per_slot"] == 8
    assert q8["wire_color_bytes_per_slot"] == 4
    assert q8["wire_depth_bytes_per_slot"] == 2
    assert f32["ici_bytes_per_rank"] == 2 * bf["ici_bytes_per_rank"]
    assert f32["ici_bytes_per_rank"] == 4 * q8["ici_bytes_per_rank"]
    assert f32["stream_bytes_per_rank"] == q8["stream_bytes_per_rank"]
    # ring wire bytes shrink identically (same fragments, same links)
    ring = modeled_exchange_traffic(8, 16, 720, 1280, k_out=16,
                                    mode="ring", wire="qpack8")
    assert ring["ici_bytes_per_rank"] == q8["ici_bytes_per_rank"]


# ------------------------------------- distributed builders × exchange modes
#
# Two-tier strategy (the 870 s tier-1 budget rules out compiling every
# builder × exchange × wire end to end — 42 full-pipeline jits):
#
# 1. The FULL wire × exchange quality matrix runs on a composite-only
#    SPMD step over fixed per-rank VDI streams (the production
#    `_composite_exchanged` under `shard_map`, exactly what
#    benchmarks/composite_bench.py times) — six small compiles exercise
#    every encode/decode × collective combination and hold the floors.
# 2. Every distributed BUILDER then gets one end-to-end threading check
#    at the widest path (qpack8 over the ring — quantize + packed lanes
#    + scale ppermute) against its own f32 reference: proves
#    `comp_cfg.wire` reaches the exchange inside that builder (generation
#    upstream of the exchange is wire-independent by construction).

_SCENE = {}


def _scene():
    if not _SCENE:
        vol = procedural_volume(16, kind="blobs")
        mesh = make_mesh(N)
        _SCENE.update(vol=vol, mesh=mesh,
                      data=shard_volume(vol.data, mesh))
    return _SCENE["vol"], _SCENE["mesh"], _SCENE["data"]


def _assert_floors(imgs, ref, label):
    """imgs: {(exchange, wire): rendered image}; every lossy image must
    hold the documented floor vs the f32 reference, every f32 image must
    match it exactly (ring f32 == all_to_all f32 == ref)."""
    for (ex, wire), img in imgs.items():
        assert np.isfinite(img).all(), (label, ex, wire)
        if wire == "f32":
            np.testing.assert_allclose(img, ref, atol=1e-6, rtol=0,
                                       err_msg=f"{label} {ex} f32")
        else:
            q = psnr(img, ref)
            assert q >= PSNR_FLOOR, f"{label} {ex}/{wire}: {q:.1f} dB"


def test_wire_exchange_matrix_composite_step():
    """Every wire × exchange combination through the production
    `_composite_exchanged` on the 8-device mesh: f32 output (both
    schedules) is bitwise the baseline composite; bf16/qpack8 hold the
    PSNR floor and the +inf empty-slot layout EXACTLY."""
    import dataclasses

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from scenery_insitu_tpu.parallel.pipeline import _composite_exchanged
    from jax import shard_map

    _, mesh, _ = _scene()
    axis = mesh.axis_names[0]
    rng = np.random.default_rng(20)
    # N ranks' sub-VDIs, depth-banded per rank (the sort-last invariant).
    # The floor contract is defined on real renders (the builder tests),
    # so the synthetic scene stays representative of one: segment extents
    # wide relative to the rank's depth span (tens of qpack8 quanta;
    # quantum-thin segments are exercised and exactly bounded by the unit
    # tests) and spatially smooth colors — with per-pixel random colors a
    # quantum-scale depth perturbation that flips one adaptive
    # resegmentation merge decision shows up as a full-scale pixel delta,
    # which no wire precision short of f32 survives.
    cs, ds = [], []
    for r in range(N):
        c, d = _stream(rng, 4, H, W, live=3, lo=1.0 + r, hi=1.6 + r,
                       ext=(0.1, 0.3))
        c = jnp.broadcast_to(c.mean(axis=(2, 3), keepdims=True), c.shape)
        cs.append(c)
        ds.append(d)
    base_c = jnp.concatenate(cs)
    base_d = jnp.concatenate(ds)
    comp = CompositeConfig(max_output_supersegments=8, adaptive_iters=2)

    outs = {}
    for ex in EXCHANGES:
        for wire in ("f32",) + LOSSY:
            cfg_m = dataclasses.replace(comp, exchange=ex, wire=wire)

            def step(color, depth, cfg_m=cfg_m):
                out = _composite_exchanged(color, depth, N, axis, cfg_m)
                return out.color, out.depth

            f = jax.jit(shard_map(
                step, mesh=mesh, in_specs=(P(axis), P(axis)),
                out_specs=(P(None, None, None, axis),
                           P(None, None, None, axis)),
                check_vma=False))
            oc, od = f(jax.device_put(base_c, NamedSharding(mesh, P(axis))),
                       jax.device_put(base_d, NamedSharding(mesh, P(axis))))
            outs[(ex, wire)] = (np.asarray(oc), np.asarray(od))

    rc, rd = outs[("all_to_all", "f32")]
    for (ex, wire), (oc, od) in outs.items():
        # empty-slot layout survives every wire (sentinel contract)
        np.testing.assert_array_equal(np.isinf(od), np.isinf(rd),
                                      err_msg=f"{ex}/{wire}")
        if wire == "f32":
            np.testing.assert_array_equal(oc, rc, err_msg=f"{ex} f32")
            fin = np.isfinite(rd)
            np.testing.assert_array_equal(od[fin], rd[fin],
                                          err_msg=f"{ex} f32")
    imgs = {k: np.asarray(render_vdi_same_view(
        VDI(jnp.asarray(c), jnp.asarray(d)))) for k, (c, d) in outs.items()}
    _assert_floors(imgs, imgs[("all_to_all", "f32")], "composite-step")


def _qpack8_ring_vs_f32(build, run, label):
    """One end-to-end threading check for a distributed builder: the
    qpack8 ring output must differ from f32 (the wire actually engaged)
    while holding the documented floor against the f32 reference."""
    ref = run(build("f32"))
    q8 = run(build("qpack8"))
    assert np.isfinite(q8).all(), label
    assert not np.array_equal(q8, ref), \
        f"{label}: qpack8 output is bitwise f32 — wire not threaded"
    q = psnr(q8, ref)
    assert q >= PSNR_FLOOR, f"{label}: {q:.1f} dB"


def _ccfg(wire, exchange="ring"):
    return CompositeConfig(max_output_supersegments=8, adaptive_iters=2,
                           exchange=exchange, wire=wire)


def test_wire_vdi_step_gather():
    """Gather-engine VDI chain threads the wire (qpack8 ring vs f32)."""
    vol, mesh, data = _scene()
    vcfg = VDIConfig(max_supersegments=6, adaptive_iters=2)
    _qpack8_ring_vs_f32(
        lambda wire: distributed_vdi_step(mesh, _tf(), W, H, vcfg,
                                          _ccfg(wire), max_steps=STEPS),
        lambda step: _render(*step(data, vol.origin, vol.spacing, _cam())),
        "gather-vdi")


@pytest.mark.parametrize("eye,exchange", [
    ((0.0, 0.2, 4.0), "ring"),          # march axis z (sharded)
    ((3.8, 0.3, 0.6), "all_to_all")])   # march axis x (in-plane)
def test_wire_mxu_step(eye, exchange):
    """MXU slice-march VDI chain, both march regimes — one regime per
    exchange schedule so both collectives see the mxu engine."""
    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.parallel.pipeline import distributed_vdi_step_mxu

    vol, mesh, data = _scene()
    cam = _cam(eye)
    vcfg = VDIConfig(max_supersegments=6, adaptive_iters=2)
    spec = slicer.make_spec(cam, vol.data.shape,
                            SliceMarchConfig(matmul_dtype="f32", scale=1.5),
                            multiple_of=N)

    def run(step):
        vdi, _ = step(data, vol.origin, vol.spacing, cam)
        return _render(vdi.color, vdi.depth)

    _qpack8_ring_vs_f32(
        lambda wire: distributed_vdi_step_mxu(mesh, _tf(), spec, vcfg,
                                              _ccfg(wire, exchange)),
        run, f"mxu-{eye}-{exchange}")


def test_wire_mxu_temporal_carry():
    """Temporal mode: the carried threshold state is UPSTREAM of the
    exchange, so it must evolve bit-identically under a lossy wire while
    the composited frames hold the floor."""
    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.parallel.pipeline import (
        distributed_initial_threshold_mxu, distributed_vdi_step_mxu_temporal)

    vol, mesh, data = _scene()
    cam = _cam()
    cfg_t = VDIConfig(max_supersegments=6, adaptive_mode="temporal")
    spec = slicer.make_spec(cam, vol.data.shape,
                            SliceMarchConfig(matmul_dtype="f32", scale=1.5),
                            multiple_of=N)
    frames, thrs = {}, {}
    for wire in ("f32", "qpack8"):
        thr = distributed_initial_threshold_mxu(
            mesh, _tf(), spec, cfg_t)(data, vol.origin, vol.spacing, cam)
        step = distributed_vdi_step_mxu_temporal(mesh, _tf(), spec, cfg_t,
                                                 _ccfg(wire))
        for _ in range(2):
            (vdi, _), thr = step(data, vol.origin, vol.spacing, cam, thr)
        frames[wire] = _render(vdi.color, vdi.depth)
        thrs[wire] = np.asarray(thr.thr)
    np.testing.assert_allclose(thrs["qpack8"], thrs["f32"], atol=1e-6,
                               rtol=0, err_msg="threshold drifted")
    assert not np.array_equal(frames["qpack8"], frames["f32"])
    q = psnr(frames["qpack8"], frames["f32"])
    assert q >= PSNR_FLOOR, f"mxu-temporal: {q:.1f} dB"


def test_wire_plain_step():
    """Plain gather-path frames (RGBA+single-depth wire): both exchange
    schedules thread the qpack8 wire."""
    vol, mesh, data = _scene()
    cfg = RenderConfig(max_steps=STEPS, early_exit_alpha=1.1)
    for ex in EXCHANGES:
        _qpack8_ring_vs_f32(
            lambda wire, ex=ex: distributed_plain_step(
                mesh, _tf(), W, H, cfg,
                comp_cfg=CompositeConfig(exchange=ex, wire=wire)),
            lambda step: np.asarray(
                step(data, vol.origin, vol.spacing, _cam())),
            f"plain-{ex}")


def test_wire_plain_mxu_step():
    """Plain MXU frames (intermediate-grid image + depth wire)."""
    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.parallel.pipeline import (
        distributed_plain_step_mxu)

    vol, mesh, data = _scene()
    cam = _cam()
    spec = slicer.make_spec(cam, vol.data.shape,
                            SliceMarchConfig(matmul_dtype="f32", scale=1.5),
                            multiple_of=N)

    def run(step):
        img, _ = step(data, vol.origin, vol.spacing, cam)
        return np.asarray(img)

    _qpack8_ring_vs_f32(
        lambda wire: distributed_plain_step_mxu(
            mesh, _tf(), spec,
            comp_cfg=CompositeConfig(exchange="ring", wire=wire)),
        run, "plain-mxu")


def test_wire_hybrid_step():
    """Hybrid volume+particle frames: the VDI half composites under the
    configured wire; the splat half is exchange-independent."""
    import jax

    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.parallel.pipeline import (
        distributed_hybrid_step_mxu)
    from scenery_insitu_tpu.parallel.particles import shard_particles

    vol, mesh, data = _scene()
    cam = _cam()
    vcfg = VDIConfig(max_supersegments=6, adaptive_iters=2)
    spec = slicer.make_spec(cam, vol.data.shape,
                            SliceMarchConfig(matmul_dtype="f32", scale=1.5),
                            multiple_of=N)
    pos = jax.random.uniform(jax.random.PRNGKey(7), (64, 3),
                             minval=-0.8, maxval=0.8)
    vel = jax.random.normal(jax.random.PRNGKey(8), (64, 3)) * 0.1
    p, v = shard_particles(pos, mesh), shard_particles(vel, mesh)

    def run(step):
        img, _ = step(data, vol.origin, vol.spacing, p, v, cam)
        return np.asarray(img)

    _qpack8_ring_vs_f32(
        lambda wire: distributed_hybrid_step_mxu(mesh, _tf(), spec, vcfg,
                                                 _ccfg(wire), radius=0.05,
                                                 stamp=3),
        run, "hybrid")


# -------------------------------------------------------------- obs counters

def test_wire_obs_counters():
    """A lossy-wire build mints wire_encode_builds + a wire_encode event,
    the ring build event carries the wire and its traffic model; an f32
    build mints NO wire counters (the fast path is structurally
    untouched)."""
    from scenery_insitu_tpu import obs

    vol, mesh, data = _scene()
    vcfg = VDIConfig(max_supersegments=6, adaptive_iters=2)

    def build(wire):
        rec = obs.Recorder(enabled=True)
        prev = obs.set_recorder(rec)
        try:
            step = distributed_vdi_step(
                mesh, _tf(), W, H, vcfg,
                CompositeConfig(max_output_supersegments=8,
                                adaptive_iters=2, exchange="ring",
                                wire=wire), max_steps=STEPS)
            step(data, vol.origin, vol.spacing, _cam())
        finally:
            obs.set_recorder(prev)
        return rec

    rec = build("qpack8")
    assert rec.counters.get("wire_encode_builds", 0) >= 1
    enc = [e for e in rec.events if e.get("name") == "wire_encode"]
    assert enc and enc[0]["attrs"]["wire"] == "qpack8"
    assert enc[0]["attrs"]["bytes_per_slot"] == 6
    builds = [e for e in rec.events
              if e.get("name") == "ring_exchange_build"]
    assert builds and builds[0]["attrs"]["wire"] == "qpack8"
    assert builds[0]["attrs"]["traffic"]["wire"] == "qpack8"

    rec32 = build("f32")
    assert rec32.counters.get("wire_encode_builds", 0) == 0


# ------------------------------------------------------- host-side quantize

def test_save_vdi_qpack8_roundtrip(tmp_path):
    """vdi_io's pre-codec quantize pass: the artifact shrinks ~4× before
    the byte codec, the precision tag lands in the metadata, and load
    dequantizes back to f32 within the wire error bound."""
    from scenery_insitu_tpu.io.vdi_io import load_vdi, save_vdi

    rng = np.random.default_rng(9)
    c, d = _stream(rng, 6, 24, 32, live=4)
    vdi = VDI(c, d)
    meta = VDIMetadata.create(np.eye(4), np.eye(4), volume_dims=(8, 8, 8),
                              window_dims=(32, 24), nw=0.1, index=3)
    raw = save_vdi(str(tmp_path / "f.npz"), vdi, meta, codec="none")
    qz = save_vdi(str(tmp_path / "q.npz"), vdi, meta, codec="none",
                  precision="qpack8")
    assert qz < raw * 0.35, (qz, raw)          # ~4× payload shrink
    back, bmeta = load_vdi(str(tmp_path / "q.npz"))
    assert int(np.asarray(bmeta.precision)) == wire_mod.WIRE_CODES["qpack8"]
    dn = np.asarray(d)
    np.testing.assert_array_equal(np.isinf(back.depth), np.isinf(dn))
    fin = np.isfinite(dn)
    span = dn[fin].max() - dn[fin].min()
    assert np.abs(back.depth[fin] - dn[fin]).max() <= 0.5 * span / 254 + 1e-5
    assert np.abs(back.color - np.asarray(c)).max() <= 0.5 / 255 + 1e-6
    # the f32 artifact still round-trips bit-exactly with precision
    fb, fmeta = load_vdi(str(tmp_path / "f.npz"))
    np.testing.assert_array_equal(fb.color, np.asarray(c))
    assert int(np.asarray(fmeta.precision)) == 0
    with pytest.raises(ValueError, match="precision"):
        save_vdi(str(tmp_path / "x.npz"), vdi, precision="u4")


def test_publisher_qpack8_quantize():
    """VDIPublisher's pre-codec quantize pass: smaller wire frames, the
    precision tag travels in header + metadata, the subscriber
    dequantizes transparently."""
    pytest.importorskip("zmq")
    pytest.importorskip("msgpack")
    import time

    from scenery_insitu_tpu.runtime.streaming import (VDIPublisher,
                                                      VDISubscriber)

    rng = np.random.default_rng(10)
    c, d = _stream(rng, 4, 12, 16, live=3)
    meta = VDIMetadata.create(np.eye(4), np.eye(4), volume_dims=(8, 8, 8),
                              window_dims=(16, 12), nw=0.1, index=7)
    with pytest.raises(ValueError, match="precision"):
        VDIPublisher("tcp://127.0.0.1:0", precision="u4")
    pub = VDIPublisher("tcp://127.0.0.1:0", codec="zlib",
                       precision="qpack8")
    sub = VDISubscriber(pub.endpoint)
    try:
        time.sleep(0.2)
        nbytes = pub.publish(VDI(c, d), meta)
        assert nbytes > 0
        got = sub.receive(timeout_ms=5000)
        assert got is not None
        rvdi, rmeta = got
        assert int(np.asarray(rmeta.precision)) == \
            wire_mod.WIRE_CODES["qpack8"]
        assert int(np.asarray(rmeta.index)) == 7
        dn = np.asarray(d)
        np.testing.assert_array_equal(np.isinf(rvdi.depth), np.isinf(dn))
        fin = np.isfinite(dn)
        span = dn[fin].max() - dn[fin].min()
        assert np.abs(rvdi.depth[fin] - dn[fin]).max() \
            <= 0.5 * span / 254 + 1e-5
        assert np.abs(rvdi.color - np.asarray(c)).max() <= 0.5 / 255 + 1e-6
    finally:
        pub.close()
        sub.close()
