"""Novel-view error study: quantify the PROXY cross-regime path (and the
sampled gather renderer) against the EXACT closed-form renderer
(ops/vdi_novel.render_vdi_exact ≅ EfficientVDIRaycast.comp:274-450) over
a view-angle sweep from the generating view around to the orthogonal
regime — the stated-bounds table VERDICT r4 item 7 asked for.

Writes a markdown table (docs/NOVEL_VIEW.md when --write-docs, else
stdout) and one JSON line with the worst-case numbers. CPU-safe.

Usage: python benchmarks/novel_view_study.py [--grid 32] [--size 80 64]
       [--write-docs]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scenery_insitu_tpu.utils.backend import pin_cpu_backend


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--grid", type=int, default=32)
    ap.add_argument("--size", type=int, nargs=2, default=(80, 64))
    ap.add_argument("--write-docs", action="store_true")
    ap.add_argument("--gather-steps", type=int, default=1200)
    args = ap.parse_args()

    import numpy as np

    from scenery_insitu_tpu.config import SliceMarchConfig, VDIConfig
    from scenery_insitu_tpu.core.camera import Camera
    from scenery_insitu_tpu.core.transfer import for_dataset
    from scenery_insitu_tpu.core.volume import procedural_volume
    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.ops.vdi_novel import (render_vdi_any,
                                                  render_vdi_exact)
    from scenery_insitu_tpu.ops.vdi_render import render_vdi
    from scenery_insitu_tpu.utils.image import psnr

    w, h = args.size
    vol = procedural_volume(args.grid, kind="blobs", seed=3)
    tf = for_dataset("procedural")
    cam0 = Camera.create((0.0, 0.3, 2.8), fov_y_deg=45.0, near=0.3,
                         far=10.0)
    spec = slicer.make_spec(cam0, vol.data.shape,
                            SliceMarchConfig(matmul_dtype="f32", scale=1.5))
    vdi, meta, axcam = slicer.generate_vdi_mxu(
        vol, tf, cam0, spec, VDIConfig(max_supersegments=8,
                                       adaptive_iters=3))

    center = np.array([0.5, 0.5, 0.5])
    r = float(np.linalg.norm(np.asarray(cam0.eye) - center))

    rows = []
    for deg in (0, 10, 20, 30, 40, 50, 60, 70, 80, 90):
        th = math.radians(deg)
        eye = center + r * np.array([math.sin(th), 0.12, math.cos(th)])
        cam1 = Camera.create(tuple(eye), fov_y_deg=45.0, near=0.3,
                             far=10.0)
        axis_new = slicer.choose_axis(cam1)[0]
        regime = "same" if axis_new == spec.axis else "cross"
        ex = np.asarray(render_vdi_exact(vdi, axcam, spec, cam1, w, h))
        pr = np.asarray(render_vdi_any(vdi, axcam, spec, cam1, w, h,
                                       num_slices=vol.data.shape[0]))
        ga = np.asarray(render_vdi(vdi, meta, cam1, w, h,
                                   steps=args.gather_steps))
        rows.append((deg, regime, psnr(pr, ex), psnr(ga, ex)))
        print(f"[study] {deg:3d} deg ({regime:5s}): proxy/sweep "
              f"{rows[-1][2]:5.1f} dB, gather {rows[-1][3]:5.1f} dB",
              file=sys.stderr, flush=True)

    lines = [
        "# Novel-view error study",
        "",
        "Ground truth: `render_vdi_exact` (closed-form in-slab path",
        "lengths, any regime — ops/vdi_novel.py; ≅ the reference's",
        "EfficientVDIRaycast.comp:274-450). The fast paths are measured",
        "against it over a horizontal orbit from the generating view",
        f"(0°) to the orthogonal regime (90°); {args.grid}^3 blobs volume,",
        f"{w}x{h} output, K=8, regenerate with",
        "`python benchmarks/novel_view_study.py --write-docs`.",
        "",
        "- **proxy/sweep** = `render_vdi_any` default: same-regime plane",
        "  sweep while the view shares the VDI's march axis, RGBA proxy",
        "  volume once it crosses regimes.",
        "- **gather** = `render_vdi` sampled march "
        f"({args.gather_steps} steps).",
        "",
        "| view angle | regime | proxy/sweep vs exact (dB) | "
        "sampled gather vs exact (dB) |",
        "|---:|---|---:|---:|",
    ]
    for deg, regime, p_pr, p_ga in rows:
        lines.append(f"| {deg}° | {regime} | {p_pr:.1f} | {p_ga:.1f} |")
    worst_pr = min(p for _, _, p, _ in rows)
    lines += [
        "",
        f"Worst proxy/sweep deviation across the sweep: **{worst_pr:.1f} "
        "dB** (floor pinned by tests/test_vdi_novel.py::"
        "test_proxy_error_bound_vs_exact).",
        "",
        "Clients that need the exact result (validation, stills) pass",
        "`exact=True` to `render_vdi_any`; the proxy stays the fast path",
        "for interactive use (one resample per received VDI, then every",
        "view is an ordinary slice march).",
    ]
    table = "\n".join(lines) + "\n"
    if args.write_docs:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "docs", "NOVEL_VIEW.md")
        with open(path, "w") as f:
            f.write(table)
        print(f"[study] wrote {path}", file=sys.stderr)
    else:
        print(table)
    print(json.dumps({
        "metric": "novel_view_proxy_vs_exact_worst_psnr",
        "value": round(worst_pr, 2), "unit": "dB",
        "config": {"grid": args.grid, "size": [w, h],
                   "angles_deg": [r0 for r0, _, _, _ in rows]},
        "rows": [{"deg": d, "regime": g, "proxy_psnr": round(p, 2),
                  "gather_psnr": round(q, 2)} for d, g, p, q in rows],
    }), flush=True)


if __name__ == "__main__":
    if os.environ.get("SITPU_BENCH_REAL") != "1":
        pin_cpu_backend()
    main()
