"""Offline dataset rendering / VDI generation — the counterpart of the
reference's VolumeFromFileExample (VolumeFromFileExample.kt:69-1116):
a raw volume file goes through `InSituSession` as a simulation's field
does — loaded in z-slabs at the file's dtype and held resident
(`runtime/session.DatasetVolumeAdapter`), the camera swept over a few
views, every frame's VDI decoded to a PNG and optionally stored and
published over ZMQ.

    python examples/volume_from_file.py --out out/                # procedural
    python examples/volume_from_file.py --dataset Kingsnake \
        --data-dir /data --out out/ --store-vdis
    python examples/volume_from_file.py --dataset beechnut \
        --data-dir /data --out out/     # the same session at u16 (PR 49)

`--dataset procedural` writes a 96^3 procedural volume as a u8 raw file
into --out first, so it takes the same path as a scan.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="procedural",
                    help="named raw dataset (core.volume dims table) or "
                         "'procedural'")
    ap.add_argument("--data-dir", default=".")
    ap.add_argument("--out", default="out")
    ap.add_argument("--width", type=int, default=0,
                    help="accepted for older command lines; the PNG is "
                         "the VDI decoded on the march's own grid")
    ap.add_argument("--height", type=int, default=0, help="as --width")
    ap.add_argument("--views", type=int, default=5)
    ap.add_argument("--store-vdis", action="store_true")
    ap.add_argument("--publish", default="",
                    help="ZMQ bind address to stream generated VDIs")
    ap.add_argument("--k", type=int, default=20, help="max supersegments")
    args = ap.parse_args()

    import numpy as np

    from scenery_insitu_tpu.config import FrameworkConfig
    import jax.numpy as jnp

    from scenery_insitu_tpu.core.camera import Camera, orbit
    from scenery_insitu_tpu.core.vdi import VDI, render_vdi_same_view
    from scenery_insitu_tpu.core.volume import procedural_volume
    from scenery_insitu_tpu.io.vdi_io import save_vdi
    from scenery_insitu_tpu.runtime.session import (DatasetVolumeAdapter,
                                                    InSituSession)
    from scenery_insitu_tpu.utils.image import save_png

    os.makedirs(args.out, exist_ok=True)
    overrides = ["slicer.engine=mxu", "vdi.adaptive_mode=temporal",
                 f"vdi.max_supersegments={args.k}",
                 f"composite.max_output_supersegments={args.k}",
                 f"runtime.dataset={args.dataset}"]
    sim = None
    if args.dataset == "procedural":
        raw = np.asarray(procedural_volume(96, kind="blobs", seed=1).data)
        np.round(raw * 255).astype(np.uint8).tofile(
            os.path.join(args.out, "procedural.raw"))
        cfg = FrameworkConfig().with_overrides(
            *overrides, f"runtime.data_dir={args.out}")
        sim = DatasetVolumeAdapter(cfg, dims_xyz=(96, 96, 96),
                                   dtype=np.uint8)
    else:       # by the dims and dtype tables, built by the session
        cfg = FrameworkConfig().with_overrides(
            *overrides, f"runtime.data_dir={args.data_dir}")

    def views(index: int, payload: dict) -> None:
        vdi = VDI(jnp.asarray(payload["vdi_color"]),
                  jnp.asarray(payload["vdi_depth"]))
        save_png(os.path.join(args.out, f"view{index:03d}.png"),
                 np.asarray(render_vdi_same_view(vdi)))
        if args.store_vdis:
            save_vdi(os.path.join(args.out, f"vdi{index:03d}.npz"), vdi,
                     payload["meta"])

    sinks = [views]
    if args.publish:
        from scenery_insitu_tpu.runtime.streaming import (VDIPublisher,
                                                          stream_sink)
        sinks.append(stream_sink(VDIPublisher(args.publish)))

    cam0 = Camera.create((0.0, 0.5, 3.0), fov_y_deg=50.0, near=0.3, far=20.0)
    sess = InSituSession(cfg, sim=sim, camera=cam0, sinks=sinks)
    for i in range(args.views):
        sess.camera = orbit(cam0, 2.0 * np.pi * i / max(args.views, 1) * 0.25)
        sess.run(1)
        print(f"view {i + 1}/{args.views} done")
    sess.close()
    print(f"wrote {args.views} views to {args.out}/")


if __name__ == "__main__":
    main()
