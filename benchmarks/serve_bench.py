"""Edge-serving bench (ISSUE 13 / ROADMAP item 2; docs/SERVING.md):
the viewers/chip/frame amortization curve, p99 camera-to-pixel latency
through a real loopback server, and bytes/viewer per tier.

The claim under test is the VDI value proposition itself (PAPER.md §0):
the representation is render-once, so N viewers must cost far less than
N renders. Measured here as the per-viewer cost of one batched dispatch
(`ops.vdi_novel.render_vdi_batch`) at growing batch sizes on the proxy
tier — the per-frame proxy expansion is shared, each extra viewer adds
only its march — plus the bitwise parity verdict (batched ==
per-camera) and the serving-loop latency distribution with admission
sheds exercised (every shed lands in the embedded ledger, like every
bench artifact).

Usage::

    JAX_PLATFORMS=cpu python benchmarks/serve_bench.py \
        --out benchmarks/results/serve_bench_r13_cpu.json

The last stdout line is the artifact JSON.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _timeit(fn, iters):
    fn()                                     # warm (compile)
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--grid", type=int, default=48)
    ap.add_argument("--k", type=int, default=20,
                    help="supersegments (20 = the reference default)")
    ap.add_argument("--width", type=int, default=96)
    ap.add_argument("--height", type=int, default=72)
    ap.add_argument("--num-slices", type=int, default=48)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--requests", type=int, default=4,
                    help="camera requests per client in the latency loop")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.config import (FrameworkConfig,
                                           SliceMarchConfig, VDIConfig)
    from scenery_insitu_tpu.core.camera import Camera, orbit
    from scenery_insitu_tpu.core.transfer import for_dataset
    from scenery_insitu_tpu.core.vdi import VDI
    from scenery_insitu_tpu.core.volume import Volume, procedural_volume
    from scenery_insitu_tpu.ops import slicer, vdi_novel

    platform = jax.default_backend()
    mdt = "bf16" if platform == "tpu" else "f32"
    W, H, NS = args.width, args.height, args.num_slices

    vol = procedural_volume(args.grid, kind="blobs", seed=3)
    tf = for_dataset("procedural")
    cam0 = Camera.create((0.1, 0.3, 2.8), fov_y_deg=45.0, near=0.3,
                         far=10.0)
    spec = slicer.make_spec(cam0, vol.data.shape,
                            SliceMarchConfig(matmul_dtype=mdt, scale=1.5))
    vdi, meta, axcam = slicer.generate_vdi_mxu(
        vol, tf, cam0, spec, VDIConfig(max_supersegments=args.k,
                                       adaptive_iters=2))
    regime = slicer.choose_axis(cam0)
    cams = [orbit(cam0, 0.02 * i, 0.01 * i) for i in range(16)]

    # ------------------------------------------- amortization (proxy tier)
    # the per-frame VDI FETCH (wire receive + decompress + dequantize) is
    # part of what one batch amortizes — "one VDI fetch and one device
    # dispatch across all viewers" — so it belongs in the frame cost.
    # Timed from AFTER publish returns: the producer's quantize/compress/
    # send is the render side's bill, not the serving tier's — folding it
    # in would inflate the very fixed cost the amortization gate divides
    from scenery_insitu_tpu.runtime.streaming import (VDIPublisher,
                                                      VDISubscriber)

    fpub = VDIPublisher("tcp://127.0.0.1:0", codec="zlib",
                        precision="qpack8")
    fsub = VDISubscriber(fpub.endpoint)
    try:
        time.sleep(0.3)
        fpub.publish(vdi, meta)                       # join + warm
        assert fsub.receive(timeout_ms=10000) is not None
        acc = 0.0
        for _ in range(args.iters):
            fpub.publish(vdi, meta)
            t1 = time.perf_counter()
            got = fsub.receive(timeout_ms=10000)
            assert got is not None and not hasattr(got, "kind")
            acc += time.perf_counter() - t1
        t_fetch = acc / args.iters
    finally:
        fpub.close()
        fsub.close()

    build = jax.jit(lambda c, d, ax: vdi_novel.vdi_to_rgba_volume(
        VDI(c, d), ax, spec, num_slices=NS))
    proxy = jax.block_until_ready(build(vdi.color, vdi.depth, axcam))
    t_build = _timeit(lambda: jax.block_until_ready(
        build(vdi.color, vdi.depth, axcam)), args.iters)
    # serve.march_scale=1.0: the proxy is pre-shaded at VDI resolution
    spec_new = slicer.make_spec(cam0, proxy.data.shape[-3:],
                                SliceMarchConfig(matmul_dtype=mdt,
                                                 scale=1.0),
                                axis_sign=regime)

    def batch_fn(n):
        stacked = vdi_novel.stack_cameras(cams[:n])
        f = jax.jit(lambda pd, po, ps, cs: vdi_novel.render_vdi_batch(
            None, None, spec, cs, W, H, tier="proxy",
            proxy=Volume(pd, po, ps), spec_new=spec_new))
        return lambda: jax.block_until_ready(
            f(proxy.data, proxy.origin, proxy.spacing, stacked))

    curve = {}
    for n in (1, 2, 4, 8, 16):
        t_batch = _timeit(batch_fn(n), args.iters)
        per_frame = t_fetch + t_build + t_batch
        curve[str(n)] = {
            "batch_ms": round(t_batch * 1e3, 2),
            "frame_ms": round(per_frame * 1e3, 2),
            "per_viewer_ms": round(per_frame / n * 1e3, 3),
            "viewers_per_second": round(n / per_frame, 1),
        }
    ratio16 = (curve["16"]["per_viewer_ms"] / curve["1"]["per_viewer_ms"])

    # one exact-tier point for the tier-cost ladder (small batch — the
    # exact tier unrolls, so its compile cost scales with the bucket)
    f_exact = jax.jit(lambda c, d, ax, cs: vdi_novel.render_vdi_batch(
        VDI(c, d), ax, spec, cs, W, H, tier="exact"))
    st2 = vdi_novel.stack_cameras(cams[:2])
    t_exact2 = _timeit(lambda: jax.block_until_ready(
        f_exact(vdi.color, vdi.depth, axcam, st2)), 1)

    # ------------------------------------------------------ parity verdict
    b = np.asarray(batch_fn(4)()[:4])
    single = jax.jit(lambda pd, po, ps, c: vdi_novel.render_vdi_proxy(
        Volume(pd, po, ps), c, W, H, spec_new))
    s = np.stack([np.asarray(single(proxy.data, proxy.origin,
                                    proxy.spacing, c)) for c in cams[:4]])
    parity_proxy = bool(np.array_equal(b, s))
    be = np.asarray(f_exact(vdi.color, vdi.depth, axcam, st2))
    se = np.stack([np.asarray(jax.jit(
        lambda c, d, ax, cc: vdi_novel.render_vdi_exact(
            VDI(c, d), ax, spec, cc, W, H))(vdi.color, vdi.depth, axcam,
                                            c)) for c in cams[:2]])
    parity_exact = bool(np.array_equal(be, se))

    # --------------------------------------- loopback latency + sheds
    from scenery_insitu_tpu.runtime.streaming import VDIPublisher
    from scenery_insitu_tpu.serve import (ServeDrop, ViewerClient,
                                          ViewerFrame, ViewerServer)

    cfg = FrameworkConfig().with_overrides(
        f"serve.width={W}", f"serve.height={H}",
        f"serve.num_slices={NS}", f"serve.max_viewers={args.clients}",
        f"serve.batch_size={max(args.clients, 1)}",
        f"serve.buckets={json.dumps(sorted({1, 2, 4, 8, args.clients}))}",
        "serve.client_timeout_s=120")
    pub = VDIPublisher("tcp://127.0.0.1:0", codec="zlib")
    srv = ViewerServer(cfg, connect=pub.endpoint, bind="tcp://127.0.0.1:0")
    # tier mix weighted toward the cheap tiers (one exact client per 4 —
    # the exact tier is the quality reference, not the scale path)
    tiers = ["proxy", "wire", "proxy", "exact"]
    clients = [ViewerClient(srv.endpoint, tier=tiers[i % 4])
               for i in range(args.clients)]
    shed_client = None
    latencies = []
    lat_by_tier = {}
    bytes_by_tier = {}
    sheds_seen = 0
    try:
        time.sleep(0.3)
        pub.publish(vdi, meta._replace(index=np.int32(0)))
        deadline = time.monotonic() + 60
        while srv.frame is None and time.monotonic() < deadline:
            srv.pump_stream(timeout_ms=100)
        assert srv.frame is not None, "server never adopted the frame"
        # hello handshake (tier negotiation) before the timed rounds
        for c in clients:
            c.hello(timeout_ms=0)
        welcomed = set()
        deadline = time.monotonic() + 30
        while len(welcomed) < len(clients) \
                and time.monotonic() < deadline:
            srv.run_once(timeout_ms=5)
            for c in clients:
                got = c.poll(timeout_ms=0)
                if isinstance(got, dict) and got.get("type") == "welcome":
                    welcomed.add(c.identity)
        assert len(welcomed) == len(clients), "hello handshake incomplete"
        for r in range(args.requests):
            t_sent = {}
            for i, c in enumerate(clients):
                c.request(orbit(cam0, 0.02 * i + 0.005 * r, 0.01 * i))
                t_sent[c.identity] = time.perf_counter()
            pending = set(t_sent)
            deadline = time.monotonic() + 120
            while pending and time.monotonic() < deadline:
                srv.run_once(timeout_ms=5)
                for c in clients:
                    if c.identity not in pending:
                        continue
                    got = c.poll(timeout_ms=0)
                    if isinstance(got, ViewerFrame):
                        dt = time.perf_counter() - t_sent[c.identity]
                        latencies.append(dt)
                        lat_by_tier.setdefault(got.tier, []).append(dt)
                        bytes_by_tier.setdefault(got.tier,
                                                 got.wire_bytes)
                        pending.discard(c.identity)
            assert not pending, f"unanswered clients in round {r}"
        # admission shed: one client past max_viewers (ledgered, typed)
        shed_client = ViewerClient(srv.endpoint, tier="proxy")
        shed_client.hello(timeout_ms=0)
        deadline = time.monotonic() + 30
        while sheds_seen == 0 and time.monotonic() < deadline:
            srv.run_once(timeout_ms=5)
            got = shed_client.poll(timeout_ms=0)
            if isinstance(got, ServeDrop) and got.kind == "shed":
                sheds_seen = 1
        server_stats = dict(srv.stats)
    finally:
        for c in clients:
            c.close()
        if shed_client is not None:
            shed_client.close()
        srv.close()
        pub.close()

    lat_ms = sorted(x * 1e3 for x in latencies)

    def quantile(values, q):
        return values[min(len(values) - 1, int(q * (len(values) - 1)))]

    pick = lambda q: quantile(lat_ms, q)
    ledger = obs.ledger()
    verdicts = {
        "amortization_n16_leq_0p25x": ratio16 <= 0.25,
        "parity_proxy_bitwise": parity_proxy,
        "parity_exact_bitwise": parity_exact,
        "sheds_ledgered_not_raised": sheds_seen == 1 and any(
            e["component"] == "serve.shed" for e in ledger),
    }
    out = {
        "metric": "serve_bench",
        "value": round(ratio16, 4),
        "unit": "per_viewer_cost_ratio_n16_vs_n1",
        "platform": platform,
        "config": {"grid": args.grid, "k": args.k, "width": W,
                   "height": H, "num_slices": NS,
                   "vdi_shape": list(np.asarray(vdi.color).shape),
                   "proxy_shape": list(np.asarray(proxy.data).shape),
                   "clients": args.clients, "requests": args.requests,
                   "iters": args.iters},
        "amortization": {"fetch_ms": round(t_fetch * 1e3, 2),
                         "proxy_build_ms": round(t_build * 1e3, 2),
                         "proxy": curve,
                         "exact_batch2_ms": round(t_exact2 * 1e3, 2)},
        "latency_ms": {"n": len(lat_ms), "p50": round(pick(0.50), 2),
                       "p90": round(pick(0.90), 2),
                       "p99": round(pick(0.99), 2),
                       "max": round(lat_ms[-1], 2),
                       "by_tier_p99": {
                           t: round(quantile(sorted(x * 1e3 for x in v),
                                             0.99), 2)
                           for t, v in sorted(lat_by_tier.items())}},
        "bytes_per_viewer": {t: int(b) for t, b in
                             sorted(bytes_by_tier.items())},
        "server_stats": server_stats,
        "verdicts": verdicts,
        "degradations": ledger,
    }
    blob = json.dumps(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(json.dumps(out, indent=2) + "\n")
    print(blob, flush=True)
    # exit code gates the CORRECTNESS verdicts only — the amortization
    # ratio is a measurement (the committed artifact documents it; a
    # noisy shared runner must not flip a timing number into a failure)
    hard = ("parity_proxy_bitwise", "parity_exact_bitwise",
            "sheds_ledgered_not_raised")
    return 0 if all(verdicts[k] for k in hard) else 1


if __name__ == "__main__":
    raise SystemExit(main())
