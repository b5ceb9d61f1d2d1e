"""Novel-view VDI renderer benchmark: the MXU plane-sweep client
(ops/vdi_novel.render_vdi_mxu) vs the portable per-step gather renderer
(ops/vdi_render.render_vdi) at display resolution — the reference's
EfficientVDIRaycast role (SURVEY.md §2d).

Prints one JSON line with both times and the speedup. Inputs are chained
across iterations (the camera pose advances and consumes the previous
frame's checksum) so no execution-dedup layer can fake the timing.

Usage: python benchmarks/novel_view_bench.py [--grid 256] [--width 1280]
       [--height 720] [--iters 5] [--skip-gather]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--grid", type=int, default=256)
    ap.add_argument("--width", type=int, default=1280)
    ap.add_argument("--height", type=int, default=720)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--gather-steps", type=int, default=256)
    ap.add_argument("--skip-gather", action="store_true",
                    help="only time the MXU path (the gather path can take "
                    "minutes per frame at 720p)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from scenery_insitu_tpu.config import SliceMarchConfig, VDIConfig
    from scenery_insitu_tpu.core.camera import Camera, orbit
    from scenery_insitu_tpu.core.transfer import for_dataset
    from scenery_insitu_tpu.core.volume import procedural_volume
    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.ops.vdi_novel import render_vdi_mxu
    from scenery_insitu_tpu.ops.vdi_render import render_vdi

    g = args.grid
    vol = procedural_volume(g, kind="blobs", seed=7)
    tf = for_dataset("procedural")
    cam0 = Camera.create((0.1, 0.4, 2.9), fov_y_deg=45.0, near=0.3, far=10.0)
    spec = slicer.make_spec(cam0, vol.data.shape, SliceMarchConfig())
    vdi, meta, axcam = slicer.generate_vdi_mxu(
        vol, tf, cam0, spec, VDIConfig(max_supersegments=args.k,
                                       adaptive_iters=2))
    jax.block_until_ready(vdi.color)
    print(f"[bench] VDI {vdi.color.shape} on "
          f"{jax.default_backend()}", file=sys.stderr, flush=True)

    def timed(fn, label):
        out = fn(jnp.float32(0.0))
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        chain = jnp.float32(0.0)
        for i in range(args.iters):
            out = fn(0.03 * (i + 1) + chain * 1e-9)
            chain = out[3].sum()            # data-dependence chain
        jax.block_until_ready(chain)
        dt = (time.perf_counter() - t0) / args.iters
        print(f"[bench] {label}: {dt * 1000:.1f} ms/frame",
              file=sys.stderr, flush=True)
        return dt

    # the VDI / proxy volume ride as jit ARGUMENTS, not closures: a closed-
    # over array is baked into the HLO as a literal constant — a 256^3
    # proxy (268 MB) bloats the serialized program and its compile
    regime = slicer.choose_axis(cam0)      # host-side; yaw stays in-regime
    mxu_j = jax.jit(lambda v, ac, yaw: render_vdi_mxu(
        v, ac, spec, orbit(cam0, yaw), args.width, args.height,
        num_slices=g, axis_sign=regime))
    t_mxu = timed(lambda yaw: mxu_j(vdi, axcam, yaw), "mxu plane sweep")

    # cross-regime: a view marching a different axis goes through the
    # pre-shaded proxy volume — built ONCE per VDI, reused per view
    from scenery_insitu_tpu.ops.vdi_novel import (render_vdi_any,
                                                  vdi_to_rgba_volume)
    proxy = jax.jit(lambda v, ac: vdi_to_rgba_volume(
        v, ac, spec, num_slices=g))(vdi, axcam)
    jax.block_until_ready(proxy.data)
    cam_x = Camera.create((2.9, 0.2, 0.3), fov_y_deg=45.0, near=0.3,
                          far=10.0)
    regime_x = slicer.choose_axis(cam_x)
    cross_j = jax.jit(lambda v, ac, p, yaw: render_vdi_any(
        v, ac, spec, orbit(cam_x, yaw), args.width, args.height,
        num_slices=g, axis_sign=regime_x, proxy=p))
    t_cross = timed(lambda yaw: cross_j(vdi, axcam, proxy, yaw),
                    "cross-regime proxy")

    t_gather = None
    if not args.skip_gather:
        gather_j = jax.jit(lambda v, yaw: render_vdi(
            v, meta, orbit(cam0, yaw), args.width, args.height,
            steps=args.gather_steps))
        t_gather = timed(lambda yaw: gather_j(vdi, yaw), "gather per-step")

    print(json.dumps({
        "metric": f"novel_view_{g}c_{args.width}x{args.height}_ms",
        "value": round(t_mxu * 1000, 2),
        "unit": "ms/frame",
        "cross_regime_ms": round(t_cross * 1000, 2),
        "gather_ms": round(t_gather * 1000, 2) if t_gather else None,
        "speedup_vs_gather": round(t_gather / t_mxu, 1) if t_gather else None,
        "backend": jax.default_backend(),
        "config": {"grid": g, "k": args.k, "image": [args.width, args.height],
                   "num_slices": g, "gather_steps": args.gather_steps},
    }))


if __name__ == "__main__":
    main()
