"""Multi-view render benchmark CLI (≅ the reference's single-GPU benchmark
modes: 9 camera angles x fps CSV + screenshots — VolumeFromFileExample.kt:
765-795, DistributedVolumes.kt:527-623 — plus the camera flythrough
recorder :631-745).

Usage:
  python benchmarks/render_bench.py [--dataset procedural|gray_scott|<name>]
      [--grid 64] [--data-dir DIR] [--engine auto|mxu|gather]
      [--mode plain|vdi] [--views 9] [--frames 5] [--width 320]
      [--height 240] [--k 12] [--out-dir bench_out] [--flythrough N]
Prints the fps CSV to stdout and writes screenshots (and flythrough frames)
under --out-dir.
"""

from __future__ import annotations

import argparse
import os
import sys


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--dataset", default="procedural")
    p.add_argument("--grid", type=int, default=64)
    p.add_argument("--data-dir", default=None,
                   help="directory with <dataset>.raw for real datasets")
    p.add_argument("--engine", default="auto")
    p.add_argument("--mode", choices=["plain", "vdi"], default="plain")
    p.add_argument("--views", type=int, default=9)
    p.add_argument("--frames", type=int, default=5)
    p.add_argument("--width", type=int, default=320)
    p.add_argument("--height", type=int, default=240)
    p.add_argument("--k", type=int, default=12)
    p.add_argument("--steps", type=int, default=128)
    p.add_argument("--out-dir", default="bench_out")
    p.add_argument("--flythrough", type=int, default=0,
                   help="also record an N-frame orbit flythrough")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from scenery_insitu_tpu.config import (RenderConfig, SliceMarchConfig,
                                           VDIConfig)
    from scenery_insitu_tpu.core.camera import Camera, orbit
    from scenery_insitu_tpu.core.transfer import for_dataset
    from scenery_insitu_tpu.core.volume import (load_dataset,
                                                procedural_volume)
    from scenery_insitu_tpu.core.vdi import render_vdi_same_view
    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.ops.raycast import raycast
    from scenery_insitu_tpu.ops.vdi_gen import generate_vdi
    from scenery_insitu_tpu.runtime.benchmark import (benchmark_views,
                                                      fps_csv,
                                                      interpolate_path,
                                                      record_flythrough)

    if args.data_dir:
        vol = load_dataset(args.dataset, args.data_dir)
    elif args.dataset == "gray_scott":
        from scenery_insitu_tpu.core.volume import Volume
        from scenery_insitu_tpu.sim import grayscott as gs
        st = gs.multi_step(gs.GrayScott.init((args.grid,) * 3), 200)
        vol = Volume.centered(st.field)
    else:
        vol = procedural_volume(args.grid, kind="blobs")
    tf = for_dataset(args.dataset)
    cam0 = Camera.create((0.0, 0.5, 2.8), fov_y_deg=50.0, near=0.3, far=20.0)
    engine = slicer.resolve_engine(args.engine)
    w, h = args.width, args.height

    # one jitted render per march regime (mxu) or a single jit (gather)
    if engine == "mxu":
        cfg = SliceMarchConfig()
        compiled = {}

        # the volume rides as a jit ARGUMENT: a closed-over array bakes
        # into the HLO as a literal, bloating the program at >=256^3
        def render_plain(cam):
            regime = slicer.choose_axis(cam)
            fn = compiled.get(("p", regime))
            if fn is None:
                spec = slicer.make_spec(cam, vol.data.shape, cfg, regime)
                fn = jax.jit(lambda v, c: slicer.raycast_mxu(
                    v, tf, c, w, h, spec).image)
                compiled[("p", regime)] = fn
            return fn(vol, cam)

        def render_vdi_step(cam):
            regime = slicer.choose_axis(cam)
            fn = compiled.get(("v", regime))
            if fn is None:
                spec = slicer.make_spec(cam, vol.data.shape, cfg, regime)
                fn = jax.jit(lambda v, c: slicer.generate_vdi_mxu(
                    v, tf, c, spec,
                    VDIConfig(max_supersegments=args.k,
                              adaptive_iters=2))[0])
                compiled[("v", regime)] = fn
            return fn(vol, cam)
    else:
        rcfg = RenderConfig(width=w, height=h, max_steps=args.steps)
        plain_j = jax.jit(
            lambda v, c: raycast(v, tf, c, w, h, rcfg).image)
        vdi_j = jax.jit(
            lambda v, c: generate_vdi(v, tf, c, w, h,
                                      VDIConfig(max_supersegments=args.k,
                                                adaptive_iters=2),
                                      max_steps=args.steps)[0])
        render_plain = lambda c: plain_j(vol, c)
        render_vdi_step = lambda c: vdi_j(vol, c)

    if args.mode == "plain":
        render, to_image = render_plain, None
    else:
        render = render_vdi_step
        to_image = lambda vdi: render_vdi_same_view(vdi)

    shots = os.path.join(args.out_dir, f"{args.dataset}_{engine}_{args.mode}")
    results = benchmark_views(render, cam0, num_views=args.views,
                              frames=args.frames, screenshot_dir=shots,
                              to_image=to_image)
    csv = fps_csv(results)
    sys.stdout.write(csv)
    os.makedirs(args.out_dir, exist_ok=True)
    csv_path = os.path.join(
        args.out_dir, f"fps_{args.dataset}_{engine}_{args.mode}.csv")
    with open(csv_path, "w") as f:
        f.write(csv)

    if args.flythrough:
        keys = [orbit(cam0, jnp.float32(a))
                for a in (0.0, 1.5, 3.0, 4.5, 6.0)]
        path = interpolate_path(keys, max(1, args.flythrough // 4))
        n = record_flythrough(render_plain, path,
                              os.path.join(args.out_dir, "flythrough"))
        print(f"flythrough: {n} frames", file=sys.stderr)


if __name__ == "__main__":
    main()
