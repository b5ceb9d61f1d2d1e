"""The VDI edge-serving tier (scenery_insitu_tpu/serve; ISSUE 13):
batched-render bitwise parity, padded-bucket invariance, mixed-tier
loopback serving, camera-delta caching, admission control (sheds are
ledgered answers, not exceptions), bounded staleness, the mid-stream
join fixes, and viewer-side reprojection."""

import time

import jax
import numpy as np
import pytest

from scenery_insitu_tpu import obs
from scenery_insitu_tpu.config import (FrameworkConfig, ServeConfig,
                                       SliceMarchConfig, VDIConfig)
from scenery_insitu_tpu.core.camera import Camera, orbit
from scenery_insitu_tpu.core.transfer import for_dataset
from scenery_insitu_tpu.core.volume import procedural_volume
from scenery_insitu_tpu.ops import slicer
from scenery_insitu_tpu.ops.vdi_novel import (render_vdi_batch,
                                              render_vdi_exact,
                                              render_vdi_mxu,
                                              render_vdi_proxy,
                                              stack_cameras,
                                              vdi_to_rgba_volume)

W, H, NS = 48, 40, 24
F32 = SliceMarchConfig(matmul_dtype="f32", scale=1.5)


@pytest.fixture(scope="module")
def fixture():
    vol = procedural_volume(32, kind="blobs", seed=3)
    tf = for_dataset("procedural")
    cam0 = Camera.create((0.1, 0.3, 2.8), fov_y_deg=45.0, near=0.3,
                         far=10.0)
    spec = slicer.make_spec(cam0, vol.data.shape, F32)
    vdi, meta, axcam = slicer.generate_vdi_mxu(
        vol, tf, cam0, spec, VDIConfig(max_supersegments=8,
                                       adaptive_iters=2))
    return vol, cam0, spec, vdi, meta, axcam


def _cams(cam0, n):
    return [orbit(cam0, 0.03 * i, 0.015 * i) for i in range(n)]


# ---------------------------------------------------- batch render parity

# The sweep and proxy tiers batch under lax.map, and XLA's
# while-loop-invariant-code-motion pass lifts what does not depend on the
# camera out of the loop body. A product that the independent render fuses
# with the sum it feeds (one rounding, an FMA) is then rounded on its own,
# so under 1 % of the samples come out one unit in the last place of a
# value in [0.5, 1) apart, and none further. With the pass off
# (--xla_disable_hlo_passes=while-loop-invariant-code-motion), with the
# batch unrolled, and for a batch of one, the two are bit-equal; the pass
# is what amortizes the per-plane decode over the batch, so it stays on.
LICM_ATOL = 2.0 ** -23


def assert_equal_up_to_hoisting(batch, independent):
    np.testing.assert_allclose(batch, independent, rtol=0, atol=LICM_ATOL)
    assert np.mean(batch != independent) < 0.01


def test_batch_sweep_bitwise_vs_independent_mxu(fixture):
    """The batched N-camera render equals N independent render_vdi_mxu
    calls to the last place (the lax.map body is the unmodified
    single-camera renderer — a vmapped batch would drift ~1e-5), and a
    batch of one bitwise."""
    vol, cam0, spec, vdi, meta, axcam = fixture
    regime = slicer.choose_axis(cam0)
    cams = _cams(cam0, 4)
    b = np.asarray(jax.jit(lambda cs: render_vdi_batch(
        vdi, axcam, spec, cs, W, H, tier="sweep", num_slices=NS,
        axis_sign=regime))(stack_cameras(cams)))
    s = np.stack([np.asarray(jax.jit(lambda c: render_vdi_mxu(
        vdi, axcam, spec, c, W, H, num_slices=NS, axis_sign=regime))(c))
        for c in cams])
    assert_equal_up_to_hoisting(b, s)
    one = np.asarray(jax.jit(lambda cs: render_vdi_batch(
        vdi, axcam, spec, cs, W, H, tier="sweep", num_slices=NS,
        axis_sign=regime))(stack_cameras(cams[:1])))
    np.testing.assert_array_equal(one[0], s[0])


def test_batch_exact_bitwise_vs_independent_exact(fixture):
    vol, cam0, spec, vdi, meta, axcam = fixture
    cams = _cams(cam0, 3)
    b = np.asarray(jax.jit(lambda cs: render_vdi_batch(
        vdi, axcam, spec, cs, W, H, tier="exact"))(stack_cameras(cams)))
    s = np.stack([np.asarray(jax.jit(lambda c: render_vdi_exact(
        vdi, axcam, spec, c, W, H))(c)) for c in cams])
    np.testing.assert_array_equal(b, s)


def test_batch_proxy_bitwise_vs_independent_proxy(fixture):
    """Proxy tier: one shared vdi_to_rgba_volume expansion, per-camera
    marches — batch equals independent render_vdi_proxy calls to the
    last place (`assert_equal_up_to_hoisting`)."""
    vol, cam0, spec, vdi, meta, axcam = fixture
    regime = slicer.choose_axis(cam0)
    proxy = vdi_to_rgba_volume(vdi, axcam, spec, num_slices=NS)
    spec_new = slicer.make_spec(cam0, proxy.data.shape[-3:],
                                F32, axis_sign=regime)
    cams = _cams(cam0, 4)
    b = np.asarray(jax.jit(lambda cs: render_vdi_batch(
        None, None, spec, cs, W, H, tier="proxy", proxy=proxy,
        spec_new=spec_new))(stack_cameras(cams)))
    s = np.stack([np.asarray(jax.jit(lambda c: render_vdi_proxy(
        proxy, c, W, H, spec_new))(c)) for c in cams])
    assert_equal_up_to_hoisting(b, s)


def test_padded_bucket_invariance(fixture):
    """Padding a batch of 3 to a bucket of 4 (replicated last camera)
    leaves the real entries bit-unchanged, and each element is
    independent of what else shares the batch."""
    vol, cam0, spec, vdi, meta, axcam = fixture
    regime = slicer.choose_axis(cam0)
    cams = _cams(cam0, 3)
    f = lambda cs: render_vdi_batch(vdi, axcam, spec, cs, W, H,
                                    tier="sweep", num_slices=NS,
                                    axis_sign=regime)
    b3 = np.asarray(jax.jit(f)(stack_cameras(cams)))
    b4 = np.asarray(jax.jit(f)(stack_cameras(cams + [cams[-1]])))
    np.testing.assert_array_equal(b3, b4[:3])
    np.testing.assert_array_equal(b4[2], b4[3])        # replicated lane


def test_batch_requires_regime_for_traced_tiers(fixture):
    vol, cam0, spec, vdi, meta, axcam = fixture
    cams = stack_cameras(_cams(cam0, 2))
    with pytest.raises(ValueError, match="axis_sign"):
        render_vdi_batch(vdi, axcam, spec, cams, W, H, tier="sweep")
    with pytest.raises(ValueError, match="tier"):
        render_vdi_batch(vdi, axcam, spec, cams, W, H, tier="nope")


# ------------------------------------------------------ loopback serving


def _pump(srv, clients, cond, secs=30):
    deadline = time.monotonic() + secs
    while time.monotonic() < deadline:
        srv.run_once(timeout_ms=10)
        got = cond()
        if got is not None:
            return got
    return None


def _rebind(bind, secs=10):
    """A publisher on the port another just closed: zmq releases the
    port on its I/O thread, some time after `close` returns (a restarted
    process finds it free; a test that restarts in place has to wait)."""
    import zmq

    from scenery_insitu_tpu.runtime.streaming import VDIPublisher

    deadline = time.monotonic() + secs
    while True:
        try:
            return VDIPublisher(bind, codec="zlib")
        except zmq.ZMQError as e:
            if e.errno != zmq.EADDRINUSE or time.monotonic() > deadline:
                raise
            time.sleep(0.05)


def _serve_pair(fixture, *overrides, publish=True):
    from scenery_insitu_tpu.runtime.streaming import VDIPublisher
    from scenery_insitu_tpu.serve import ViewerServer

    vol, cam0, spec, vdi, meta, axcam = fixture
    cfg = FrameworkConfig().with_overrides(
        f"serve.width={W}", f"serve.height={H}", f"serve.num_slices={NS}",
        "serve.batch_size=8", "serve.buckets=[1,2,4,8]", *overrides)
    pub = VDIPublisher("tcp://127.0.0.1:0", codec="zlib")
    srv = ViewerServer(cfg, connect=pub.endpoint, bind="tcp://127.0.0.1:0")
    if publish:
        time.sleep(0.25)
        pub.publish(vdi, meta._replace(index=np.int32(0)))
        got = _pump(srv, (), lambda: srv.frame)
        assert got is not None, "server never adopted a frame"
    return pub, srv


def test_loopback_mixed_tier_batch(fixture):
    """One server, three tiers in one pump cycle: every client gets its
    own tier's pixels, proxy == direct render to the last place
    (`assert_equal_up_to_hoisting`: proxy and wire share a bucket of two),
    wire == the u8 quantization of the proxy answer beside it."""
    from scenery_insitu_tpu.serve import ViewerClient, ViewerFrame

    vol, cam0, spec, vdi, meta, axcam = fixture
    pub, srv = _serve_pair(fixture)
    cs = [ViewerClient(srv.endpoint, tier=t)
          for t in ("proxy", "exact", "wire")]
    try:
        novel = orbit(cam0, 0.15)
        for c in cs:
            c.hello(timeout_ms=0)
            c.request(novel)
        done = _pump(srv, cs, lambda: (
            True if all(c.last is not None
                        or isinstance(c.poll(timeout_ms=0), ViewerFrame)
                        for c in cs) and all(c.last for c in cs)
            else None))
        assert done, [c.stats for c in cs]
        fp, fe, fw = (c.last for c in cs)
        assert (fp.tier, fe.tier, fw.tier) == ("proxy", "exact", "wire")
        # proxy answer == the independent proxy render (the reference
        # takes the proxy as jit ARGUMENTS like the server does — a
        # closure constant would fold differently)
        from scenery_insitu_tpu.core.volume import Volume

        regime = slicer.choose_axis(novel)
        proxy = srv._ensure_proxy()
        spec_new = srv._spec_new_for(regime,
                                     tuple(proxy.data.shape[-3:]))
        ref = np.asarray(jax.jit(lambda pd, po, ps, c: render_vdi_proxy(
            Volume(pd, po, ps), c, W, H, spec_new))(
            proxy.data, proxy.origin, proxy.spacing, novel))
        assert_equal_up_to_hoisting(fp.image, ref)
        # wire answer is the u8 wire quantization of the same camera's
        # render in the same batch
        np.testing.assert_array_equal(
            fw.image,
            np.clip(np.round(fp.image * 255), 0, 255).astype(np.uint8)
            .astype(np.float32) / 255.0)
        # exact differs from proxy (different renderer) but is finite
        assert np.isfinite(fe.image).all() and fe.image[3].max() > 0.0
        # bytes/viewer: the wire tier ships 4x fewer bytes
        assert fp.wire_bytes == 4 * fw.wire_bytes
    finally:
        for c in cs:
            c.close()
        srv.close()
        pub.close()


def test_camera_delta_cache_and_tolerance(fixture):
    from scenery_insitu_tpu.serve import ViewerClient, ViewerFrame

    vol, cam0, spec, vdi, meta, axcam = fixture
    pub, srv = _serve_pair(fixture, "serve.cam_tol=1e-4")
    c = ViewerClient(srv.endpoint, tier="proxy")
    try:
        novel = orbit(cam0, 0.15)
        c.request(novel)
        f1 = _pump(srv, (c,), lambda: c.poll(timeout_ms=0))
        assert isinstance(f1, ViewerFrame) and not f1.cached
        # bit-identical camera -> cached answer, identical pixels
        c.request(novel)
        f2 = _pump(srv, (c,), lambda: c.poll(timeout_ms=0))
        assert f2.cached and np.array_equal(f2.image, f1.image)
        # a sub-tolerance nudge still re-serves the cache
        c.request(novel._replace(
            eye=novel.eye + np.float32(5e-5)))
        f3 = _pump(srv, (c,), lambda: c.poll(timeout_ms=0))
        assert f3.cached
        # a real move re-renders
        c.request(orbit(cam0, 0.3))
        f4 = _pump(srv, (c,), lambda: c.poll(timeout_ms=0))
        assert not f4.cached
        assert not np.array_equal(f4.image, f1.image)
        assert srv.stats["cache_hits"] == 2
        # a tier re-negotiation invalidates the cache even for the same
        # camera (the payload dtype changes — a stale f32 blob must
        # never serve a wire client)
        c.tier = "wire"
        c.hello(timeout_ms=0)
        w = _pump(srv, (c,), lambda: c.poll(timeout_ms=0))
        assert isinstance(w, dict) and w["tier"] == "wire"
        c.request(orbit(cam0, 0.3))
        f5 = _pump(srv, (c,), lambda: c.poll(timeout_ms=0))
        assert f5.tier == "wire" and not f5.cached
        assert f5.wire_bytes == f4.wire_bytes // 4
    finally:
        c.close()
        srv.close()
        pub.close()


def test_admission_shed_is_ledgered_not_raised(fixture):
    from scenery_insitu_tpu.serve import ServeDrop, ViewerClient

    pub, srv = _serve_pair(fixture, "serve.max_viewers=1")
    c1 = ViewerClient(srv.endpoint, tier="proxy")
    c2 = ViewerClient(srv.endpoint, tier="proxy")
    try:
        c1.hello(timeout_ms=0)
        w = _pump(srv, (c1,), lambda: c1.poll(timeout_ms=0))
        assert isinstance(w, dict) and w["type"] == "welcome"
        c2.hello(timeout_ms=0)
        shed = _pump(srv, (c2,), lambda: c2.poll(timeout_ms=0))
        assert isinstance(shed, ServeDrop) and shed.kind == "shed"
        assert shed.reason == "max_viewers"
        comps = [e["component"] for e in obs.ledger()]
        assert "serve.shed" in comps
        assert srv.stats["sheds"] >= 1
    finally:
        c1.close()
        c2.close()
        srv.close()
        pub.close()


def test_queue_cap_sheds_and_coalescing(fixture):
    """Requests coalesce latest-wins per client (the queue holds one
    request per client), and distinct clients beyond queue_cap shed."""
    from scenery_insitu_tpu.serve import ServeDrop, ViewerClient

    vol, cam0, spec, vdi, meta, axcam = fixture
    pub, srv = _serve_pair(fixture, "serve.queue_cap=1",
                           "serve.max_viewers=4")
    c1 = ViewerClient(srv.endpoint, tier="proxy")
    c2 = ViewerClient(srv.endpoint, tier="proxy")
    try:
        # two requests from ONE client: coalesce, no shed
        c1.request(orbit(cam0, 0.1))
        c1.request(orbit(cam0, 0.2))
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not srv.queue:
            srv.pump_clients()
            time.sleep(0.01)
        srv.pump_clients()
        assert len(srv.queue) == 1
        # a second client while the queue is full: shed
        # (intake only: a render would empty the queue before a late
        # request arrives, and then nothing is shed)
        c2.request(orbit(cam0, 0.3))
        shed, deadline = None, time.monotonic() + 10
        while shed is None and time.monotonic() < deadline:
            srv.pump_clients()
            shed = c2.poll(timeout_ms=10)
        assert isinstance(shed, ServeDrop) and shed.reason == "queue_cap"
    finally:
        c1.close()
        c2.close()
        srv.close()
        pub.close()


def test_bounded_staleness_stamped_and_ledgered(fixture):
    """Tiles of newer frames advance the stream head without completing;
    once the served VDI falls > staleness_frames behind, answers are
    stamped stale and serve.stale is minted."""
    from scenery_insitu_tpu.core.vdi import VDI as VDI_t
    from scenery_insitu_tpu.serve import ViewerClient, ViewerFrame

    vol, cam0, spec, vdi, meta, axcam = fixture
    pub, srv = _serve_pair(fixture, "serve.staleness_frames=2")
    c = ViewerClient(srv.endpoint, tier="proxy")
    try:
        # newer frames exist but never complete (one tile of two)
        color = np.asarray(vdi.color)
        depth = np.asarray(vdi.depth)
        half = VDI_t(color[..., :color.shape[-1] // 2],
                     depth[..., :depth.shape[-1] // 2])
        for f in range(1, 6):
            pub.publish_tile(half, meta._replace(index=np.int32(f)),
                             0, 2, 0)
        got = _pump(srv, (), lambda: (
            True if srv.newest is not None and srv.newest >= 5 else None))
        assert got, srv.newest
        c.request(orbit(cam0, 0.12))
        f1 = _pump(srv, (c,), lambda: c.poll(timeout_ms=0))
        assert isinstance(f1, ViewerFrame) and f1.stale
        comps = [e["component"] for e in obs.ledger()]
        assert "serve.stale" in comps
        assert srv.stats["stale_answers"] >= 1
        # a cache hit re-stamps staleness too — the cached pixels are
        # the current frame's, but the head has moved past it
        c.request(orbit(cam0, 0.12))
        f2 = _pump(srv, (c,), lambda: c.poll(timeout_ms=0))
        assert isinstance(f2, ViewerFrame) and f2.cached and f2.stale
        assert srv.stats["stale_answers"] >= 2
    finally:
        c.close()
        srv.close()
        pub.close()


def test_staleness_head_advances_through_resync_drops(fixture):
    """Regression: during a temporal-delta resync window EVERY stream
    message surfaces as a typed drop — the staleness head must advance
    from those refused frames too, or answers read stale=False for
    exactly the degraded stretch the bounded-staleness contract
    targets."""
    from scenery_insitu_tpu.config import DeltaConfig
    from scenery_insitu_tpu.runtime.streaming import VDIPublisher
    from scenery_insitu_tpu.serve import (ViewerClient, ViewerFrame,
                                          ViewerServer)
    from scenery_insitu_tpu.testing.faults import FaultSpec, inject

    vol, cam0, spec, vdi, meta, axcam = fixture
    cfg = FrameworkConfig().with_overrides(
        f"serve.width={W}", f"serve.height={H}", f"serve.num_slices={NS}",
        "serve.batch_size=8", "serve.buckets=[1,2,4,8]",
        "serve.staleness_frames=2")
    pub = VDIPublisher("tcp://127.0.0.1:0", codec="zlib",
                       precision="qpack8",
                       delta=DeltaConfig(enabled=True, iframe_period=64))
    srv = ViewerServer(cfg, connect=pub.endpoint, bind="tcp://127.0.0.1:0")
    c = ViewerClient(srv.endpoint, tier="proxy")
    try:
        time.sleep(0.25)
        pub.publish(vdi, meta._replace(index=np.int32(0)))   # I-frame
        got = _pump(srv, (), lambda: srv.frame)
        assert got is not None, "server never adopted the I-frame"
        # lose ONE message on the wire: the delta chain breaks, and with
        # iframe_period=64 every later record is a resync StreamDrop
        orig = pub.sock
        inject(pub, FaultSpec(drop=1.0))
        pub.publish(vdi, meta._replace(index=np.int32(1)))
        pub.sock = orig
        for f in range(2, 7):
            pub.publish(vdi, meta._replace(index=np.int32(f)))
        got = _pump(srv, (), lambda: (
            True if srv.stats["stream_drops"] >= 5 else None))
        assert got, srv.stats
        # the head advanced THROUGH the refused frames...
        assert srv.newest is not None and srv.newest >= 6, srv.newest
        # ...so the retained frame-0 answer is stamped stale
        c.request(orbit(cam0, 0.1))
        f1 = _pump(srv, (c,), lambda: c.poll(timeout_ms=0))
        assert isinstance(f1, ViewerFrame) and f1.stale
        assert srv.stats["stale_answers"] >= 1
    finally:
        c.close()
        srv.close()
        pub.close()


def test_garbage_camera_sender_does_not_occupy_admission(fixture):
    """Regression: a camera message that fails validation must not
    admit its sender — junk idents would otherwise fill max_viewers
    slots (renewable for client_timeout_s) and shed real viewers
    despite zero renderable load."""
    from scenery_insitu_tpu.runtime.streaming import _msgpack
    from scenery_insitu_tpu.serve import ViewerClient, ViewerFrame

    vol, cam0, spec, vdi, meta, axcam = fixture
    pub, srv = _serve_pair(fixture, "serve.max_viewers=1")
    junk = ViewerClient(srv.endpoint, tier="proxy")
    c = ViewerClient(srv.endpoint, tier="proxy")
    try:
        # a garbage camera (non-finite eye) and a garbage seq: dropped
        # typed, and the sender is NOT admitted
        junk.sock.send(_msgpack().packb(
            {"type": "camera", "eye": "junk", "seq": 1}))
        junk.sock.send(_msgpack().packb(
            {"type": "camera", "eye": [0.0, 0.0, 3.0], "seq": "nope"}))
        # finite-but-degenerate: zero fov, inverted clip range — would
        # burn a full batched render producing a garbage frame
        junk.sock.send(_msgpack().packb(
            {"type": "camera", "eye": [0.0, 0.0, 3.0], "fov_y": 0.0,
             "near": 0.0, "far": -1.0, "seq": 2}))
        got = _pump(srv, (), lambda: (
            True if srv.stats["client_drops"] >= 3 else None))
        assert got, srv.stats
        assert len(srv.clients) == 0, "junk sender occupies a slot"
        # the one real viewer still fits under max_viewers=1
        c.request(orbit(cam0, 0.1))
        f = _pump(srv, (c,), lambda: c.poll(timeout_ms=0))
        assert isinstance(f, ViewerFrame)
        assert srv.stats["sheds"] == 0, srv.stats
    finally:
        junk.close()
        c.close()
        srv.close()
        pub.close()


def test_request_without_hello_honors_tier(fixture):
    """Regression: a viewer that never says hello is implicitly
    admitted — its constructor tier must ride the camera request, not
    silently downgrade to serve.default_tier."""
    from scenery_insitu_tpu.serve import ViewerClient, ViewerFrame

    vol, cam0, spec, vdi, meta, axcam = fixture
    pub, srv = _serve_pair(fixture)
    c = ViewerClient(srv.endpoint, tier="wire")
    try:
        c.request(orbit(cam0, 0.1))
        f = _pump(srv, (c,), lambda: c.poll(timeout_ms=0))
        assert isinstance(f, ViewerFrame) and f.tier == "wire"
        assert f.wire_bytes == W * H * 4          # u8, not f32
    finally:
        c.close()
        srv.close()
        pub.close()


def test_client_refuses_frame_answer_missing_fields():
    """Regression: a corrupt-but-parseable frame answer (missing
    frame/seq/tier/stale/cached) is a typed ServeDrop, never an
    exception — the stated ViewerClient hardening contract."""
    from scenery_insitu_tpu.runtime.streaming import _msgpack, _zmq
    from scenery_insitu_tpu.serve import ServeDrop, ViewerClient

    zmq = _zmq()
    router = zmq.Context.instance().socket(zmq.ROUTER)
    port = router.bind_to_random_port("tcp://127.0.0.1")
    c = ViewerClient(f"tcp://127.0.0.1:{port}", tier="proxy")
    try:
        c.heartbeat()                       # teach the router the ident
        ident, _ = router.recv_multipart()
        blob = np.zeros((4, 2, 2), np.float32).tobytes()
        router.send_multipart([ident, _msgpack().packb(
            {"type": "frame", "shape": [4, 2, 2], "dtype": "f32"}),
            blob])
        got = c.poll(timeout_ms=5000)
        assert isinstance(got, ServeDrop) and got.kind == "malformed"
        assert c.stats["drops"] == 1
    finally:
        c.close()
        router.close(linger=0)


def test_client_heartbeat_pacer():
    """maybe_heartbeat fires only after fault.heartbeat_period_s of
    send silence (the PR-11 pacer convention)."""
    from scenery_insitu_tpu.config import FaultConfig
    from scenery_insitu_tpu.runtime.streaming import _zmq
    from scenery_insitu_tpu.serve import ViewerClient

    zmq = _zmq()
    router = zmq.Context.instance().socket(zmq.ROUTER)
    port = router.bind_to_random_port("tcp://127.0.0.1")
    c = ViewerClient(f"tcp://127.0.0.1:{port}",
                     fault=FaultConfig(heartbeat_period_s=0.2))
    try:
        assert not c.maybe_heartbeat()      # just constructed: quiet
        time.sleep(0.25)
        assert c.maybe_heartbeat()          # past the period: fires
        assert not c.maybe_heartbeat()      # freshly sent: quiet again
    finally:
        c.close()
        router.close(linger=0)


def test_unknown_tier_degrades_to_default(fixture):
    from scenery_insitu_tpu.serve import ViewerClient

    pub, srv = _serve_pair(fixture, publish=False)
    c = ViewerClient(srv.endpoint, tier="hologram")
    try:
        c.hello(timeout_ms=0)
        w = _pump(srv, (c,), lambda: c.poll(timeout_ms=0), secs=10)
        assert isinstance(w, dict) and w["tier"] == "proxy"
        comps = [e["component"] for e in obs.ledger()]
        assert "serve.tier" in comps
    finally:
        c.close()
        srv.close()
        pub.close()


def test_malformed_client_message_is_contained(fixture):
    """Garbage on the client socket drops typed (serve.client) and the
    server keeps serving the well-behaved viewer."""
    from scenery_insitu_tpu.serve import ViewerClient, ViewerFrame

    vol, cam0, spec, vdi, meta, axcam = fixture
    pub, srv = _serve_pair(fixture)
    c = ViewerClient(srv.endpoint, tier="proxy")
    try:
        c.sock.send(b"\xc1\x00\xff not msgpack")
        c.sock.send(b"\x00" * (srv.fault.max_message_bytes + 1))
        c.request(orbit(cam0, 0.1))
        f = _pump(srv, (c,), lambda: c.poll(timeout_ms=0))
        assert isinstance(f, ViewerFrame)
        assert srv.stats["client_drops"] >= 2
        comps = [e["component"] for e in obs.ledger()]
        assert "serve.client" in comps
    finally:
        c.close()
        srv.close()
        pub.close()


# ------------------------------------------------- mid-stream join fixes


def test_receive_assembles_tile_streams(fixture):
    """Bugfix (ISSUE 13): VDISubscriber.receive on a TILE-granular
    stream returns whole assembled frames, never a mislabeled column
    block; a mid-stream join waits for the next complete frame."""
    from scenery_insitu_tpu.core.vdi import VDI as VDI_t
    from scenery_insitu_tpu.runtime.streaming import (VDIPublisher,
                                                      VDISubscriber)

    vol, cam0, spec, vdi, meta, axcam = fixture
    color = np.asarray(vdi.color)
    depth = np.asarray(vdi.depth)
    wb = color.shape[-1] // 2
    tiles = [VDI_t(color[..., i * wb:(i + 1) * wb],
                   depth[..., i * wb:(i + 1) * wb]) for i in range(2)]
    pub = VDIPublisher("tcp://127.0.0.1:0", codec="zlib")
    sub = VDISubscriber(pub.endpoint)
    try:
        time.sleep(0.25)
        # mid-frame join shape: the subscriber sees only tile 1 of
        # frame 0, then both tiles of frame 1
        pub.publish_tile(tiles[1], meta._replace(index=np.int32(0)),
                         1, 2, wb)
        for t in range(2):
            pub.publish_tile(tiles[t], meta._replace(index=np.int32(1)),
                             t, 2, t * wb)
        got = sub.receive(timeout_ms=5000)
        assert got is not None and not hasattr(got, "kind")
        rvdi, rmeta = got
        assert int(np.asarray(rmeta.index)) == 1      # frame 0 never done
        assert rvdi.color.shape == color.shape        # FULL width
        np.testing.assert_array_equal(np.asarray(rvdi.color), color)
    finally:
        pub.close()
        sub.close()


def test_mid_stream_delta_join_waits_for_iframe(fixture):
    """A subscriber joining a temporal-delta stream mid-flight sees
    typed resync drops (never an exception) until the next I-frame,
    then clean frames."""
    from scenery_insitu_tpu.config import DeltaConfig
    from scenery_insitu_tpu.runtime.streaming import (StreamDrop,
                                                      VDIPublisher,
                                                      VDISubscriber)

    vol, cam0, spec, vdi, meta, axcam = fixture
    pub = VDIPublisher("tcp://127.0.0.1:0", codec="zlib",
                       precision="qpack8", epoch=5,
                       delta=DeltaConfig(enabled=True, iframe_period=4))
    # consume the stream head so the encoder is past its first I-frame
    for i in range(2):
        pub.publish(vdi, meta._replace(index=np.int32(i)))
    sub = VDISubscriber(pub.endpoint)    # mid-stream join
    try:
        time.sleep(0.25)
        good, resyncs = None, 0
        for i in range(2, 8):
            pub.publish(vdi, meta._replace(index=np.int32(i)))
            got = sub.receive(timeout_ms=3000)
            if isinstance(got, StreamDrop):
                assert got.kind == "resync"
                resyncs += 1
                continue
            if got is not None:
                good = got
                break
        assert good is not None, "never recovered within iframe_period"
        assert resyncs >= 1                 # first contact was a P/SKIP
        assert sub.stats["resyncs"] == resyncs
        np.testing.assert_allclose(np.asarray(good[0].color),
                                   np.asarray(vdi.color), atol=0.05)
    finally:
        pub.close()
        sub.close()


def test_gather_vdi_served_with_derived_plane_count(fixture):
    """Regression (found driving the session chain): gather-engine VDIs
    (the session default on CPU) have their reconstructed plane ladder
    start at the camera NEAR PLANE, well before the volume — a fixed
    serve.num_slices that stops short serves blank proxy frames. The
    default (0) derives the count from the frame's own depth range and
    must produce content."""
    from scenery_insitu_tpu.ops.vdi_gen import generate_vdi
    from scenery_insitu_tpu.serve import ViewerClient, ViewerFrame

    vol, cam0, spec, _, _, _ = fixture
    tf = for_dataset("procedural")
    gvdi, gmeta = generate_vdi(vol, tf, cam0, 64, 48,
                               VDIConfig(max_supersegments=6,
                                         adaptive_iters=2), max_steps=96)
    pub, srv = _serve_pair(fixture, "serve.num_slices=0", publish=False)
    c = ViewerClient(srv.endpoint, tier="proxy")
    try:
        time.sleep(0.25)
        pub.publish(gvdi, gmeta)
        got = _pump(srv, (), lambda: srv.frame)
        assert got is not None
        # derived count reaches past the near-plane gap to the content
        assert srv.frame["num_slices"] > 24
        c.request(orbit(cam0, 0.1))
        f = _pump(srv, (c,), lambda: c.poll(timeout_ms=0))
        assert isinstance(f, ViewerFrame)
        assert float(f.image[3].max()) > 0.05, "blank proxy frame"
    finally:
        c.close()
        srv.close()
        pub.close()


def test_server_survives_publisher_restart(fixture):
    """A publisher restart (new epoch, frame indices reset) must reset
    the server's OWN assembler and stream-head tracking: without the
    mirror reset, the late-tile guard wedges assembly (new indices sit
    below the old head) and every answer reads stale forever."""
    from scenery_insitu_tpu.serve import ViewerClient, ViewerFrame

    vol, cam0, spec, vdi, meta, axcam = fixture
    pub, srv = _serve_pair(fixture, publish=False)
    c = ViewerClient(srv.endpoint, tier="proxy")
    try:
        time.sleep(0.25)
        # first incarnation runs far ahead; answer once (fills the cache)
        pub.publish(vdi, meta._replace(index=np.int32(500)))
        got = _pump(srv, (), lambda: srv.frame)
        assert got is not None and srv.frame["index"] == 500
        c.request(orbit(cam0, 0.1))
        f0 = _pump(srv, (c,), lambda: c.poll(timeout_ms=0))
        assert isinstance(f0, ViewerFrame) and not f0.cached
        # restart: new epoch, indices restart near zero
        pub.close()
        pub2 = _rebind(pub.endpoint.replace("127.0.0.1", "*"))
        time.sleep(0.25)
        deadline = time.monotonic() + 15
        while (srv.frame["index"] != 1
               and time.monotonic() < deadline):
            pub2.publish(vdi, meta._replace(index=np.int32(1)))
            srv.pump_stream(timeout_ms=200)
        assert srv.frame["index"] == 1, srv.frame["index"]
        assert srv.newest == 1                     # head reset with it
        # same camera as before the restart: the cache is keyed by the
        # ADOPTION id, so the old incarnation's blob must not re-serve
        c.request(orbit(cam0, 0.1))
        f = _pump(srv, (c,), lambda: c.poll(timeout_ms=0))
        assert isinstance(f, ViewerFrame) and not f.stale
        assert not f.cached
        pub2.close()
    finally:
        c.close()
        srv.close()
        pub.close()


def test_server_joins_tile_stream_mid_frame(fixture):
    """The serve subscriber path end to end: a server that joins a tile
    stream mid-frame only ever adopts COMPLETE frames."""
    from scenery_insitu_tpu.core.vdi import VDI as VDI_t

    vol, cam0, spec, vdi, meta, axcam = fixture
    pub, srv = _serve_pair(fixture, publish=False)
    try:
        time.sleep(0.25)
        color = np.asarray(vdi.color)
        depth = np.asarray(vdi.depth)
        wb = color.shape[-1] // 2
        tiles = [VDI_t(color[..., i * wb:(i + 1) * wb],
                       depth[..., i * wb:(i + 1) * wb]) for i in range(2)]
        pub.publish_tile(tiles[1], meta._replace(index=np.int32(3)),
                         1, 2, wb)                    # mid-frame join
        for t in range(2):
            pub.publish_tile(tiles[t], meta._replace(index=np.int32(4)),
                             t, 2, t * wb)
        got = _pump(srv, (), lambda: srv.frame)
        assert got is not None
        assert srv.frame["index"] == 4
        assert srv.frame["vdi"].color.shape == color.shape
    finally:
        srv.close()
        pub.close()


# --------------------------------------------------- viewer reprojection


def test_reproject_identity_is_noop(fixture):
    from scenery_insitu_tpu.serve import reproject_planar

    vol, cam0, spec, vdi, meta, axcam = fixture
    img = np.asarray(render_vdi_exact(vdi, axcam, spec, cam0, W, H))
    rep = reproject_planar(img, cam0, cam0)
    np.testing.assert_allclose(rep, img, atol=1e-3)


def test_reproject_small_move_beats_stale_image(fixture):
    """The warped image approximates the true novel view better than
    re-showing the unwarped old frame — the whole point of play (c).
    Translation is the motion planar reprojection exists for (an orbit
    about the look-at target keeps the old image nearly centered, so
    the stale frame is already close there)."""
    import jax.numpy as jnp

    from scenery_insitu_tpu.serve import reproject_planar
    from scenery_insitu_tpu.utils.image import psnr

    vol, cam0, spec, vdi, meta, axcam = fixture
    shift = jnp.asarray([0.1, 0.0, 0.0], jnp.float32)
    cam1 = cam0._replace(eye=cam0.eye + shift, target=cam0.target + shift)
    old = np.asarray(render_vdi_exact(vdi, axcam, spec, cam0, W, H))
    true = np.asarray(render_vdi_exact(vdi, axcam, spec, cam1, W, H))
    warped = reproject_planar(old, cam0, cam1)
    assert np.isfinite(warped).all()
    assert psnr(warped, true) > psnr(old, true) + 3.0


def test_serve_config_validation():
    from scenery_insitu_tpu.serve import ViewerServer

    with pytest.raises(ValueError, match="buckets"):
        ServeConfig(buckets=(4, 2, 1))
    with pytest.raises(ValueError, match="default_tier"):
        ServeConfig(default_tier="fast")
    with pytest.raises(ValueError, match="max_viewers"):
        ServeConfig(max_viewers=0)
    cfg = FrameworkConfig().with_overrides("serve.max_viewers=128",
                                           "serve.default_tier=wire")
    assert cfg.serve.max_viewers == 128
    assert cfg.serve.default_tier == "wire"
    # the buckets/batch_size pair is order-INSENSITIVE through
    # with_overrides (cross-field validity is judged on the final
    # config, at the consumer) ...
    a = FrameworkConfig().with_overrides("serve.buckets=[1,2,4]",
                                         "serve.batch_size=4")
    b = FrameworkConfig().with_overrides("serve.batch_size=4",
                                         "serve.buckets=[1,2,4]")
    assert a.serve == b.serve
    # ... and an inconsistent FINAL pair is refused where it is consumed
    bad = FrameworkConfig().with_overrides("serve.buckets=[1,2,4]")
    with pytest.raises(ValueError, match="batch_size"):
        ViewerServer(bad, connect="tcp://localhost:1",
                     bind="tcp://127.0.0.1:0")
