"""Ambient-occlusion demo (≅ the inactive AO scaffolding in the
reference's ComputeRaycast.comp:147-191, turned into a working TPU-native
feature — see ops/ao.py): renders a procedural volume with and without AO
on both engines and writes the four PNGs side by side.

    python examples/ao_render.py --out out_ao/ [--strength 0.8] [--radius 4]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="out_ao")
    ap.add_argument("--grid", type=int, default=96)
    ap.add_argument("--width", type=int, default=480)
    ap.add_argument("--height", type=int, default=360)
    ap.add_argument("--strength", type=float, default=0.8)
    ap.add_argument("--radius", type=int, default=4)
    ap.add_argument("--steps", type=int, default=192)
    args = ap.parse_args()

    from scenery_insitu_tpu.utils.backend import enable_compile_cache

    enable_compile_cache()

    import numpy as np

    from scenery_insitu_tpu.config import RenderConfig, SliceMarchConfig
    from scenery_insitu_tpu.core.camera import Camera
    from scenery_insitu_tpu.core.transfer import for_dataset
    from scenery_insitu_tpu.core.volume import procedural_volume
    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.ops.ao import shade_volume_ao
    from scenery_insitu_tpu.ops.raycast import raycast
    from scenery_insitu_tpu.utils.image import save_png

    os.makedirs(args.out, exist_ok=True)
    vol = procedural_volume(args.grid, kind="blobs", seed=5)
    tf = for_dataset("procedural")
    cam = Camera.create((0.5, 0.8, 2.6), fov_y_deg=50.0, near=0.3, far=20.0)
    bg = (1.0, 1.0, 1.0, 1.0)
    w, h = args.width, args.height

    cfg = RenderConfig(max_steps=args.steps, background=bg)
    cfg_ao = RenderConfig(max_steps=args.steps, background=bg,
                          ao_strength=args.strength, ao_radius=args.radius)
    save_png(os.path.join(args.out, "gather_plain.png"),
             np.asarray(raycast(vol, tf, cam, w, h, cfg).image))
    save_png(os.path.join(args.out, "gather_ao.png"),
             np.asarray(raycast(vol, tf, cam, w, h, cfg_ao).image))

    spec = slicer.make_spec(cam, vol.data.shape, SliceMarchConfig())
    save_png(os.path.join(args.out, "mxu_plain.png"),
             np.asarray(slicer.raycast_mxu(vol, tf, cam, w, h, spec,
                                           background=bg).image))
    shaded = shade_volume_ao(vol, tf, args.radius, args.strength)
    save_png(os.path.join(args.out, "mxu_ao.png"),
             np.asarray(slicer.raycast_mxu(shaded, None, cam, w, h, spec,
                                           background=bg).image))
    print(f"wrote 4 images to {args.out}/")


if __name__ == "__main__":
    main()
