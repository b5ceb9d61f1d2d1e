"""Temporal-delta VDI streams (docs/PERF.md "Temporal deltas"):
the P-frame wire codec must reconstruct BIT-EXACTLY vs the qpack8-only
publish (SKIP/residual/I-tile), recover through forced I-tiles after an
injected drop (testing/faults.ChaosSocket), and never SKIP a tile whose
codes changed; the dirty-tile re-march (CompositeConfig.temporal_reuse
= "ranges") must be bitwise vs recompute in exact mode on both the
frame and waves schedules, conservative on range-moving changes, and
ledger itself inert where no fragment can be carried."""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from scenery_insitu_tpu.config import (CompositeConfig, DeltaConfig,
                                       FrameworkConfig, SliceMarchConfig,
                                       VDIConfig)
from scenery_insitu_tpu.core.camera import Camera
from scenery_insitu_tpu.core.transfer import TransferFunction
from scenery_insitu_tpu.core.vdi import VDI, VDIMetadata
from scenery_insitu_tpu.ops import delta as dl
from scenery_insitu_tpu.parallel.mesh import make_mesh
from scenery_insitu_tpu.parallel.pipeline import (
    distributed_initial_reuse_mxu, distributed_initial_threshold_mxu,
    distributed_vdi_step_mxu, distributed_vdi_step_mxu_temporal,
    shard_volume)

N = 8
ATOL = 1e-5     # separately-compiled programs carry ~1-ulp fusion noise


def _zmq_ok():
    try:
        import zmq  # noqa: F401
        return True
    except ImportError:
        return False


needs_zmq = pytest.mark.skipif(not _zmq_ok(), reason="pyzmq not installed")


# ===================================================== code-space residuals


def test_diff_apply_runs_roundtrip():
    rng = np.random.default_rng(3)
    prev = rng.integers(0, 2**31, 257, dtype=np.int64).astype(np.uint32)
    cur = prev.copy()
    for lo, hi in ((3, 9), (40, 41), (100, 160), (250, 257)):
        cur[lo:hi] = rng.integers(0, 2**31, hi - lo).astype(np.uint32)
    s, l, v = dl.diff_runs(prev, cur)
    # runs are maximal: every listed slot really changed, boundaries hold
    assert int(l.sum()) == v.size == int((prev != cur).sum())
    out = dl.apply_runs(prev, s, l, v)
    assert np.array_equal(out, cur)


def test_diff_runs_identical_and_validation():
    a = np.arange(10, dtype=np.uint16)
    s, l, v = dl.diff_runs(a, a.copy())
    assert s.size == l.size == v.size == 0
    assert np.array_equal(dl.apply_runs(a, s, l, v), a)
    with pytest.raises(ValueError, match="disagree"):
        dl.diff_runs(a, a.astype(np.uint32))
    with pytest.raises(ValueError, match="values"):
        dl.apply_runs(a, np.asarray([1], np.uint32),
                      np.asarray([3], np.uint32),
                      np.asarray([7], np.uint16))


def _codes(rng, shape=(3, 4, 6)):
    return (rng.integers(0, 2**31, shape).astype(np.uint32),
            rng.integers(0, 2**15, shape).astype(np.uint16))


def test_encoder_skip_p_i_modes():
    rng = np.random.default_rng(0)
    enc = dl.DeltaEncoder(iframe_period=100)
    c, d = _codes(rng)
    r0 = enc.encode(0, c, d, 0.0, 1.0)
    assert r0.mode == "I" and r0.reason == "first"
    # unchanged → SKIP, zero wire bytes
    r1 = enc.encode(0, c, d, 0.0, 1.0)
    assert r1.mode == "SKIP" and r1.wire_bytes == 0 \
        and r1.base_gen == r0.gen
    # one code flips → sparse P, decoder round-trips bit-exactly
    c2 = c.copy()
    c2.ravel()[5] ^= 0xFF
    r2 = enc.encode(0, c2, d, 0.0, 1.0)
    assert r2.mode == "P" and r2.wire_bytes < r2.full_bytes
    dec = dl.DeltaDecoder()
    for r in (r0, r1, r2):
        got = dec.apply(0, r.mode, r.gen, r.base_gen, r.c_payload,
                        r.d_payload, r.scale)
        assert got is not None
    cc, dd, near, far = got
    assert np.array_equal(cc, c2) and np.array_equal(dd, d)
    # a fully re-randomized tile makes the residual dense → I wins
    c3, d3 = _codes(rng)
    r3 = enc.encode(0, c3, d3, 0.0, 1.0)
    assert r3.mode == "I" and r3.reason == "dense_residual"


def test_encoder_scale_change_is_not_a_skip():
    """Equal codes under a DIFFERENT [near, far] dequantize to different
    depths — the encoder must not SKIP them."""
    rng = np.random.default_rng(1)
    enc = dl.DeltaEncoder()
    c, d = _codes(rng)
    enc.encode(0, c, d, 0.0, 1.0)
    r = enc.encode(0, c, d, 0.0, 2.0)
    assert r.mode != "SKIP"


def test_encoder_forced_iframe_period_and_reset():
    from scenery_insitu_tpu import obs

    rng = np.random.default_rng(2)
    rec = obs.Recorder(enabled=True)
    prev = obs.set_recorder(rec)
    try:
        enc = dl.DeltaEncoder(iframe_period=3)
        c, d = _codes(rng)
        modes = [enc.encode(0, c, d, 0.0, 1.0).mode for _ in range(7)]
        # I, SKIP, I(periodic), SKIP, SKIP→ period forces every 3rd
        assert modes[0] == "I" and modes.count("I") >= 3 \
            and "SKIP" in modes
        assert enc.stats["forced_i"] >= 2
        enc.reset()
        r = enc.encode(0, c, d, 0.0, 1.0)
        assert r.mode == "I" and r.reason == "reset"
    finally:
        obs.set_recorder(prev)
    assert rec.counters.get("iframe_forced", 0) >= 3
    assert rec.counters.get("delta_tiles_skipped", 0) >= 1
    assert rec.counters.get("delta_bytes_saved", 0) > 0


def test_encoder_never_skips_changed_codes():
    """Conservativeness property: ANY code change — one bit anywhere —
    must not SKIP, and the decoder must reconstruct it bit-exactly."""
    rng = np.random.default_rng(4)
    enc = dl.DeltaEncoder(iframe_period=10**6)
    dec = dl.DeltaDecoder()
    c, d = _codes(rng, (4, 8, 8))
    r = enc.encode(0, c, d, 0.0, 1.0)
    dec.apply(0, r.mode, r.gen, r.base_gen, r.c_payload, r.d_payload,
              r.scale)
    for _ in range(24):
        which = rng.integers(0, 2)
        c, d = c.copy(), d.copy()
        if which == 0:
            c.ravel()[rng.integers(0, c.size)] ^= np.uint32(
                1 << int(rng.integers(0, 32)))
        else:
            d.ravel()[rng.integers(0, d.size)] ^= np.uint16(
                1 << int(rng.integers(0, 16)))
        r = enc.encode(0, c, d, 0.0, 1.0)
        assert r.mode != "SKIP"
        got = dec.apply(0, r.mode, r.gen, r.base_gen, r.c_payload,
                        r.d_payload, r.scale)
        assert got is not None
        assert np.array_equal(got[0], c) and np.array_equal(got[1], d)


def test_decoder_resync_on_broken_chain():
    rng = np.random.default_rng(5)
    enc = dl.DeltaEncoder(iframe_period=10**6)
    dec = dl.DeltaDecoder()
    c, d = _codes(rng)
    r0 = enc.encode(0, c, d, 0.0, 1.0)
    dec.apply(0, r0.mode, r0.gen, r0.base_gen, r0.c_payload,
              r0.d_payload, r0.scale)
    c1 = c.copy(); c1.ravel()[0] ^= 1
    r1 = enc.encode(0, c1, d, 0.0, 1.0)              # P — "lost"
    c2 = c1.copy(); c2.ravel()[1] ^= 1
    r2 = enc.encode(0, c2, d, 0.0, 1.0)              # P on top of r1
    got = dec.apply(0, r2.mode, r2.gen, r2.base_gen, r2.c_payload,
                    r2.d_payload, r2.scale)
    assert got is None and dec.stats["resync"] == 1
    # the decoder is purely chain-driven: the "lost" record arriving
    # late (its base still matches) repairs the chain — in the live
    # protocol the subscriber's stale-seq drop refuses such replays
    # before they reach the decoder, so this is the recovery path for
    # reordering, not a replay hole
    got1 = dec.apply(0, r1.mode, r1.gen, r1.base_gen, r1.c_payload,
                     r1.d_payload, r1.scale)
    assert got1 is not None and np.array_equal(got1[0], c1)
    # and an I-tile always re-anchors regardless of chain state
    ri = dl.DeltaEncoder(iframe_period=10**6)
    rI = ri.encode(0, c2, d, 0.0, 1.0)
    assert dec.apply(0, rI.mode, rI.gen, rI.base_gen, rI.c_payload,
                     rI.d_payload, rI.scale) is not None


def test_pack_unpack_delta_blobs_roundtrip():
    from scenery_insitu_tpu.io.vdi_io import (decompress,
                                              delta_expected_bytes,
                                              pack_delta_blobs,
                                              unpack_delta_payload)

    rng = np.random.default_rng(6)
    enc = dl.DeltaEncoder(iframe_period=10**6)
    c, d = _codes(rng, (2, 5, 7))
    recs = [enc.encode(0, c, d, 0.0, 1.0)]
    recs.append(enc.encode(0, c, d, 0.0, 1.0))               # SKIP
    c2 = c.copy(); c2.ravel()[3:6] ^= 9
    recs.append(enc.encode(0, c2, d, 0.0, 1.0))              # P
    dec = dl.DeltaDecoder()
    for r in recs:
        h, cb, db = pack_delta_blobs(r, codec="zlib")
        craw = decompress(cb, "zlib") if cb else b""
        draw = decompress(db, "zlib") if db else b""
        assert (len(craw), len(draw)) == delta_expected_bytes(
            h, c.shape, d.shape)
        cp, dp = unpack_delta_payload(h, craw, draw, c.shape, d.shape)
        got = dec.apply(0, h["mode"], h["gen"], h["base"], cp, dp,
                        r.scale)
        assert got is not None
    assert np.array_equal(got[0], c2) and np.array_equal(got[1], d)


def test_modeled_delta_traffic():
    m = dl.modeled_delta_traffic(20, 720, 1280, skip_frac=0.6,
                                 p_frac=0.2, residual_frac=0.1,
                                 iframe_period=8)
    assert m["delta_bytes_per_frame"] < 0.4 * m["qpack8_bytes_per_frame"]
    full = dl.modeled_delta_traffic(20, 720, 1280, skip_frac=0.0)
    assert full["delta_bytes_per_frame"] == \
        full["qpack8_bytes_per_frame"]
    with pytest.raises(ValueError):
        dl.modeled_delta_traffic(20, 720, 1280, skip_frac=0.9,
                                 p_frac=0.2)


# ========================================================== stream plumbing


def _meta(i=0, w=24, h=16):
    return VDIMetadata.create(
        projection=np.eye(4, dtype=np.float32),
        view=np.eye(4, dtype=np.float32), volume_dims=(8, 8, 8),
        window_dims=(w, h), nw=1.0, index=i)


def _frames(seed=0, n=6, K=4, H=16, W=24):
    """A slow-evolving synthetic stream: frames 0-2 identical, then a
    localized change, then identical again."""
    rng = np.random.default_rng(seed)
    c = np.clip(rng.random((K, 4, H, W)), 0, 1).astype(np.float32)
    d = np.sort(rng.random((K, 2, H, W)).astype(np.float32), axis=1)
    out = []
    for i in range(n):
        ci, di = c.copy(), d.copy()
        if i >= 3:
            ci[:, :, :4, :4] = 0.9
        out.append(VDI(ci, di))
    return out


@needs_zmq
def test_stream_delta_bitwise_vs_plain_publish():
    """The delta stream decodes BIT-IDENTICALLY to the qpack8-only
    stream, while SKIP frames cost a small fraction of the bytes."""
    from scenery_insitu_tpu.runtime.streaming import (VDIPublisher,
                                                      VDISubscriber)

    pub_d = VDIPublisher(bind="tcp://127.0.0.1:0", codec="zlib",
                         precision="qpack8", epoch=11,
                         delta=DeltaConfig(enabled=True, iframe_period=16))
    pub_p = VDIPublisher(bind="tcp://127.0.0.1:0", codec="zlib",
                         precision="qpack8", epoch=12)
    sub_d = VDISubscriber(connect=pub_d.endpoint)
    sub_p = VDISubscriber(connect=pub_p.endpoint)
    time.sleep(0.3)
    try:
        sizes_d, sizes_p = [], []
        for i, v in enumerate(_frames()):
            m = _meta(i)
            sizes_d.append(pub_d.publish(v, m))
            sizes_p.append(pub_p.publish(v, m))
            got_d = sub_d.receive(timeout_ms=3000)
            got_p = sub_p.receive(timeout_ms=3000)
            assert got_d is not None and not hasattr(got_d, "kind")
            vd, md = got_d
            vp, mp = got_p
            assert np.array_equal(np.asarray(vd.color),
                                  np.asarray(vp.color))
            assert np.array_equal(np.asarray(vd.depth),
                                  np.asarray(vp.depth))
            assert int(np.asarray(md.index)) == i
        # frames 1, 2 are SKIPs; frame 4+ too (identical to 3)
        st = pub_d.delta_stats
        assert st["skip"] >= 3 and st["i"] >= 1
        assert sizes_d[1] < sizes_p[1] / 3
        assert sub_d._delta.stats["skip"] >= 3
    finally:
        for s in (pub_d, pub_p, sub_d, sub_p):
            s.close()


@needs_zmq
def test_stream_delta_tiles_assemble_bitwise():
    """Per-tile delta records (the PR-8 column block is the dirty unit)
    reassemble through the PR-11 FrameAssembler bit-exactly; unchanged
    tiles SKIP even while other tiles of the same frame change."""
    from scenery_insitu_tpu.runtime.streaming import (FrameAssembler,
                                                      VDIPublisher,
                                                      VDISubscriber)

    pub = VDIPublisher(bind="tcp://127.0.0.1:0", codec="zlib",
                       precision="qpack8", epoch=21,
                       delta=DeltaConfig(enabled=True, iframe_period=32))
    sub = VDISubscriber(connect=pub.endpoint)
    time.sleep(0.3)
    tiles = 4
    try:
        frames = _frames(seed=7, n=5)
        asm = FrameAssembler(window=4)
        done = {}
        for i, v in enumerate(frames):
            m = _meta(i)
            w = v.color.shape[-1]
            wb = w // tiles
            for t in range(tiles):
                pub.publish_tile(
                    VDI(v.color[..., t * wb:(t + 1) * wb],
                        v.depth[..., t * wb:(t + 1) * wb]),
                    m, t, tiles, t * wb)
            for _ in range(tiles):
                got = sub.receive_tile(timeout_ms=3000)
                assert got is not None and not hasattr(got, "kind")
                out = asm.add(*got)
                if out is not None:
                    done[int(np.asarray(out[1].index))] = out[0]
        assert sorted(done) == list(range(5))
        # bit-exact vs the qpack8 quantize→dequantize of the source
        from scenery_insitu_tpu.ops.wire import (qpack8_dequantize_np,
                                                 qpack8_quantize_np)
        for i, v in enumerate(frames):
            w = v.color.shape[-1]
            wb = w // tiles
            ref_c, ref_d = [], []
            for t in range(tiles):
                qc, qd, near, far = qpack8_quantize_np(
                    np.asarray(v.color[..., t * wb:(t + 1) * wb]),
                    np.asarray(v.depth[..., t * wb:(t + 1) * wb]))
                c, d = qpack8_dequantize_np(qc, qd, near, far)
                ref_c.append(c)
                ref_d.append(d)
            assert np.array_equal(np.asarray(done[i].color),
                                  np.concatenate(ref_c, axis=-1))
            assert np.array_equal(np.asarray(done[i].depth),
                                  np.concatenate(ref_d, axis=-1))
        # frame 3 changed only the first columns: tiles past the change
        # SKIP even though the frame as a whole changed
        st = pub.delta_stats
        assert st["skip"] >= 3 * tiles - 3
    finally:
        pub.close()
        sub.close()


@needs_zmq
def test_delta_requires_qpack8():
    from scenery_insitu_tpu.runtime.streaming import VDIPublisher

    with pytest.raises(ValueError, match="qpack8"):
        VDIPublisher(bind="tcp://127.0.0.1:0", precision="f32",
                     delta=DeltaConfig(enabled=True))


@needs_zmq
def test_forced_i_recovery_after_injected_drop():
    """ChaosSocket drops messages on the wire; the subscriber refuses
    orphaned P/SKIP records as ``resync`` StreamDrops (ledgered
    stream.delta_resync) and recovers on the next forced I-tile — every
    frame that DOES decode is bit-exact vs the clean stream."""
    from scenery_insitu_tpu.runtime.streaming import (VDIPublisher,
                                                      VDISubscriber)
    from scenery_insitu_tpu.testing.faults import ChaosSocket, FaultSpec

    period = 3
    pub = VDIPublisher(bind="tcp://127.0.0.1:0", codec="zlib",
                       precision="qpack8", epoch=31,
                       delta=DeltaConfig(enabled=True,
                                         iframe_period=period))
    ref = VDIPublisher(bind="tcp://127.0.0.1:0", codec="zlib",
                       precision="qpack8", epoch=32)
    sub = VDISubscriber(connect=pub.endpoint)
    sub_ref = VDISubscriber(connect=ref.endpoint)
    time.sleep(0.3)
    pub.sock = ChaosSocket(pub.sock, FaultSpec(drop=0.35), seed=5)
    rng = np.random.default_rng(9)
    K, H, W = 3, 12, 16
    base_c = rng.random((K, 4, H, W)).astype(np.float32)
    base_d = np.sort(rng.random((K, 2, H, W)).astype(np.float32), axis=1)
    try:
        decoded, reference = {}, {}
        for i in range(14):
            c = base_c.copy()
            c[:, :, i % H, :] = (i % 5) / 5.0       # slow evolution
            v = VDI(c, base_d)
            m = _meta(i, w=W, h=H)
            pub.publish(v, m)
            ref.publish(v, m)
            got = sub.receive(timeout_ms=500)
            r = sub_ref.receive(timeout_ms=3000)
            assert r is not None
            reference[i] = r[0]
            if got is not None and not hasattr(got, "kind"):
                decoded[int(np.asarray(got[1].index))] = got[0]
        inj = pub.sock.report.injected
        assert inj.get("drop", 0) >= 1            # chaos actually fired
        assert len(decoded) >= 3                  # the stream recovered
        # a drop orphans its successors until the next I: either a
        # resync was refused or only I-frames happened to survive
        assert sub.stats["resyncs"] >= 1 or sub.stats["gaps"] >= 1
        # the frames that decoded are bit-exact — a resync wait can skip
        # frames but can never corrupt one
        for i, v in decoded.items():
            assert np.array_equal(np.asarray(v.color),
                                  np.asarray(reference[i].color))
            assert np.array_equal(np.asarray(v.depth),
                                  np.asarray(reference[i].depth))
        # recovery bound: after any miss, an I arrives within `period`
        # frames, so gaps between consecutive decoded indexes stay small
        idx = sorted(decoded)
        assert max(np.diff(idx), default=1) <= 2 * period
    finally:
        for s in (pub, ref, sub, sub_ref):
            s.close()


@needs_zmq
def test_epoch_change_resets_delta_state():
    """A restarted publisher (new epoch) must not patch residuals onto
    the old incarnation's tiles: the subscriber resets its decoder on
    the epoch change and the new stream's first I re-anchors it."""
    from scenery_insitu_tpu.runtime.streaming import (VDIPublisher,
                                                      VDISubscriber)

    v = _frames(n=1)[0]
    pub1 = VDIPublisher(bind="tcp://127.0.0.1:0", codec="zlib",
                        precision="qpack8", epoch=41,
                        delta=DeltaConfig(enabled=True))
    sub = VDISubscriber(connect=pub1.endpoint)
    time.sleep(0.3)
    try:
        pub1.publish(v, _meta(0))
        assert sub.receive(timeout_ms=3000) is not None
        assert sub._delta._state            # retained tile
        pub1.close()
        # the successor publisher (fresh epoch); the SUB socket joins
        # its endpoint — same stream identity from the subscriber's view
        pub2 = VDIPublisher(bind="tcp://127.0.0.1:0",
                            codec="zlib", precision="qpack8", epoch=42,
                            delta=DeltaConfig(enabled=True))
        sub.sock.connect(pub2.endpoint)
        time.sleep(0.4)
        pub2.publish(v, _meta(1))
        got = sub.receive(timeout_ms=3000)
        assert got is not None and not hasattr(got, "kind")
        assert sub.stats["epoch_changes"] == 1
        # state was rebuilt from the NEW stream's I-tile
        assert list(sub._delta._state.values())[0][0] == 1
        pub2.close()
    finally:
        sub.close()


# ================================================== dirty-tile re-marching


def _scene(n=N, size=32):
    rng = np.random.default_rng(0)
    field = np.zeros((size, size, size), np.float32)
    field[4:12, 8:24, 8:24] = rng.random((8, 16, 16)).astype(np.float32)
    tf = TransferFunction.ramp(0.1, 0.9, 0.8, "hot")
    cam = Camera.create((0.0, 0.4, 2.5))
    origin = jnp.asarray([-1.0, -1.0, -1.0], jnp.float32)
    spacing = jnp.full((3,), 2.0 / size, jnp.float32)
    return field, tf, cam, origin, spacing


def _spec(cam, shape, scale=1.0):
    from scenery_insitu_tpu.ops import slicer

    return slicer.make_spec(cam, shape, SliceMarchConfig(scale=scale),
                            multiple_of=2 * N)


@pytest.mark.parametrize("schedule", ["frame", "waves"])
def test_reuse_exact_mode_bitwise(schedule):
    """range_tol=0 + static camera + static field: frame 2 skips every
    march and is BITWISE equal to frame 1 AND to the reuse-off step —
    on both schedules."""
    mesh = make_mesh(N)
    field, tf, cam, origin, spacing = _scene()
    vdi_cfg = VDIConfig(max_supersegments=6, adaptive_mode="histogram")
    spec = _spec(cam, field.shape)
    kw = dict(schedule=schedule, wave_tiles=2) if schedule == "waves" \
        else {}
    cc_on = CompositeConfig(max_output_supersegments=6,
                            temporal_reuse="ranges", **kw)
    cc_off = CompositeConfig(max_output_supersegments=6, **kw)
    step_on = distributed_vdi_step_mxu(mesh, tf, spec, vdi_cfg, cc_on)
    step_off = distributed_vdi_step_mxu(mesh, tf, spec, vdi_cfg, cc_off)
    rseed = distributed_initial_reuse_mxu(mesh, tf, spec, vdi_cfg, cc_on)
    f = shard_volume(jnp.asarray(field), mesh)
    ref, _ = step_off(f, origin, spacing, cam)
    ru = rseed(f, origin, spacing, cam)
    assert not np.asarray(ru.valid).any()
    (v1, m1), ru1 = step_on(f, origin, spacing, cam, ru)
    assert np.asarray(ru1.dirty).all()          # first frame marches
    (v2, m2), ru2 = step_on(f, origin, spacing, cam, ru1)
    assert not np.asarray(ru2.dirty).any()      # second frame skips
    assert np.array_equal(np.asarray(v2.color), np.asarray(v1.color))
    assert np.array_equal(np.asarray(v2.depth), np.asarray(v1.depth))
    # reuse-on equals reuse-off bitwise (the cond's march branch is the
    # same computation; holds on this backend — the waves/frame cross-
    # schedule comparison keeps the usual 1e-5 fusion gate elsewhere)
    assert np.array_equal(np.asarray(v1.color), np.asarray(ref.color))
    assert np.array_equal(np.asarray(v1.depth), np.asarray(ref.depth))


def test_reuse_parity_across_schedules():
    """Exact-mode reuse output on the waves schedule matches the frame
    schedule within the standard cross-schedule fusion gate."""
    mesh = make_mesh(N)
    field, tf, cam, origin, spacing = _scene()
    vdi_cfg = VDIConfig(max_supersegments=6, adaptive_mode="histogram")
    spec = _spec(cam, field.shape)
    outs = {}
    for schedule in ("frame", "waves"):
        kw = dict(schedule=schedule, wave_tiles=2) \
            if schedule == "waves" else {}
        cc = CompositeConfig(max_output_supersegments=6,
                             temporal_reuse="ranges", **kw)
        step = distributed_vdi_step_mxu(mesh, tf, spec, vdi_cfg, cc)
        rseed = distributed_initial_reuse_mxu(mesh, tf, spec, vdi_cfg,
                                              cc)
        f = shard_volume(jnp.asarray(field), mesh)
        ru = rseed(f, origin, spacing, cam)
        (v1, _), ru1 = step(f, origin, spacing, cam, ru)
        (v2, _), _ = step(f, origin, spacing, cam, ru1)
        outs[schedule] = v2
    np.testing.assert_allclose(np.asarray(outs["frame"].color),
                               np.asarray(outs["waves"].color),
                               atol=ATOL, rtol=0)


def test_reuse_dirty_conservative_on_range_motion():
    """Changed brick ⇒ never SKIP: a value pushed OUTSIDE its cell's
    retained [lo, hi] must dirty exactly the owning rank, and the
    output must equal the reuse-off recompute."""
    mesh = make_mesh(N)
    field, tf, cam, origin, spacing = _scene()
    vdi_cfg = VDIConfig(max_supersegments=6, adaptive_mode="histogram")
    spec = _spec(cam, field.shape)
    cc = CompositeConfig(max_output_supersegments=6,
                         temporal_reuse="ranges")
    step = distributed_vdi_step_mxu(mesh, tf, spec, vdi_cfg, cc)
    step_off = distributed_vdi_step_mxu(
        mesh, tf, spec, vdi_cfg, CompositeConfig(
            max_output_supersegments=6))
    rseed = distributed_initial_reuse_mxu(mesh, tf, spec, vdi_cfg, cc)
    f = shard_volume(jnp.asarray(field), mesh)
    ru = rseed(f, origin, spacing, cam)
    (_, _), ru = step(f, origin, spacing, cam, ru)
    # perturb one voxel per target rank ABOVE the global max — the
    # containing cell's hi must move, so the rank must re-march
    for z, rank in ((5, 1), (21, 5), (30, 7)):
        f2 = field.copy()
        f2[z, 16, 16] = 2.0
        fd = shard_volume(jnp.asarray(f2), mesh)
        (v, _), ru = step(fd, origin, spacing, cam, ru)
        d = np.asarray(ru.dirty)
        assert d[rank] == 1, (z, rank, d)
        ref, _ = step_off(fd, origin, spacing, cam)
        assert np.array_equal(np.asarray(v.color), np.asarray(ref.color))
        field = f2


def test_reuse_camera_move_dirties_every_rank():
    mesh = make_mesh(N)
    field, tf, cam, origin, spacing = _scene()
    vdi_cfg = VDIConfig(max_supersegments=6, adaptive_mode="histogram")
    spec = _spec(cam, field.shape)
    cc = CompositeConfig(max_output_supersegments=6,
                         temporal_reuse="ranges")
    step = distributed_vdi_step_mxu(mesh, tf, spec, vdi_cfg, cc)
    rseed = distributed_initial_reuse_mxu(mesh, tf, spec, vdi_cfg, cc)
    f = shard_volume(jnp.asarray(field), mesh)
    (_, _), ru = step(f, origin, spacing, cam,
                      rseed(f, origin, spacing, cam))
    cam2 = Camera.create((0.05, 0.4, 2.5))
    (_, _), ru2 = step(f, origin, spacing, cam2, ru)
    assert np.asarray(ru2.dirty).all()


def test_reuse_range_tol_hysteresis():
    """Sub-tolerance range drift keeps skipping, accumulates against
    the last MARCHED signature, and re-marches once the accumulated
    drift crosses range_tol."""
    mesh = make_mesh(N)
    field, tf, cam, origin, spacing = _scene()
    vdi_cfg = VDIConfig(max_supersegments=6, adaptive_mode="histogram")
    spec = _spec(cam, field.shape)
    cc = CompositeConfig(max_output_supersegments=6,
                         temporal_reuse="ranges")
    step = distributed_vdi_step_mxu(mesh, tf, spec, vdi_cfg, cc,
                                    reuse_tol=0.3)
    rseed = distributed_initial_reuse_mxu(mesh, tf, spec, vdi_cfg, cc)
    f0 = field.copy()
    f0[20, 16, 16] = 1.2            # rank 5's cell hi anchor
    f = shard_volume(jnp.asarray(f0), mesh)
    (_, _), ru = step(f, origin, spacing, cam,
                      rseed(f, origin, spacing, cam))
    # +0.2 < tol: clean; the signature stays anchored at the marched
    # frame, so another +0.2 (total 0.4 > tol) re-marches
    for bump, want_dirty in ((0.2, 0), (0.4, 1)):
        f2 = f0.copy()
        f2[20, 16, 16] = 1.2 + bump
        (_, _), ru = step(shard_volume(jnp.asarray(f2), mesh), origin,
                          spacing, cam, ru)
        assert np.asarray(ru.dirty)[5] == want_dirty, bump


def test_reuse_inert_ledger_on_unsupported_builders():
    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.parallel.pipeline import (
        distributed_plain_step, distributed_vdi_step)

    mesh = make_mesh(N)
    _, tf, cam, origin, spacing = _scene()
    rec = obs.Recorder(enabled=True)
    prev = obs.set_recorder(rec)
    try:
        distributed_vdi_step(mesh, tf, 32, 32, VDIConfig(
            max_supersegments=4), CompositeConfig(
            temporal_reuse="ranges"))
        distributed_plain_step(mesh, tf, 32, 32, comp_cfg=CompositeConfig(
            temporal_reuse="ranges"))
    finally:
        obs.set_recorder(prev)
    rows = [e for e in obs.ledger() if e["component"] == "delta.reuse"]
    assert rows and rows[0]["from"] == "ranges"


class _FrozenSim:
    """A static volume sim: the slow-evolving limit — every frame after
    the first must skip every rank."""

    kind = "frozen"

    def __init__(self, field):
        self._f = jnp.asarray(field)

    def advance(self, n: int) -> None:
        pass

    @property
    def field(self):
        return self._f


def test_session_reuse_counters_and_bitwise_frames(tmp_path):
    """A traced session with temporal_reuse="ranges" on a static scene:
    delta_march_skipped counts every post-first-frame tile, the dirty
    histogram event fires, and the fetched frames are bitwise equal."""
    from scenery_insitu_tpu.runtime.session import InSituSession

    field, tf, cam, origin, spacing = _scene()
    cfg = FrameworkConfig().with_overrides(
        "composite.temporal_reuse=ranges",
        "composite.max_output_supersegments=6",
        "vdi.max_supersegments=6",
        "vdi.adaptive_mode=histogram",
        "slicer.engine=mxu",         # CPU 'auto' resolves to gather
        "slicer.scale=1.0",
        "obs.enabled=true",
        "sim.grid=[32,32,32]")
    frames = {}
    sess = InSituSession(cfg, sim=_FrozenSim(field), tf=tf,
                         camera=cam,
                         sinks=[lambda i, p: frames.update(
                             {i: (p["vdi_color"], p["vdi_depth"])})])
    sess.run(4)
    assert sorted(frames) == [0, 1, 2, 3]
    for i in (1, 2, 3):
        assert np.array_equal(frames[i][0], frames[0][0])
        assert np.array_equal(frames[i][1], frames[0][1])
    # frames 1..3 skipped all 8 ranks' marches (frame 0 marched; its
    # decision is read one frame later, so >= 2 frames' worth count)
    assert sess.obs.counters.get("delta_march_skipped", 0) >= 2 * N
    evs = [e for e in sess.obs.events
           if e.get("name") == "delta_dirty_tiles"]
    assert evs and evs[-1]["attrs"]["skipped_tiles"] == N
    assert sess.obs.counters.get("reuse_steps_built", 0) >= 1


def test_config_validation():
    with pytest.raises(ValueError, match="temporal_reuse"):
        CompositeConfig(temporal_reuse="bogus")
    with pytest.raises(ValueError, match="iframe_period"):
        DeltaConfig(iframe_period=0)
    with pytest.raises(ValueError, match="range_tol"):
        DeltaConfig(range_tol=-1.0)
    with pytest.raises(ValueError, match="iframe_period"):
        dl.DeltaEncoder(iframe_period=0)
