"""Capture a device-side xprof trace of the steady-state flagship frame
(VERDICT r2 item 1: "one committed xprof trace of a steady-state frame ...
showing where the ms go"). The frame is the same fused program bench.py
times (sim advance → temporal MXU VDI generate → composite), so the trace
is the op-level breakdown behind the headline number — open with
xprof / tensorboard.

    python benchmarks/profile_frame.py [--grid 256] [--frames 10]
        [--out benchmarks/results/trace_r3]

Writes <out>/plugins/profile/**/*.xplane.pb plus a one-line JSON summary
on stdout. Off-TPU it still runs (CPU trace) for smoke-testing.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--grid", type=int, default=256)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--frames", type=int, default=10)
    ap.add_argument("--sim-steps", type=int, default=10)
    ap.add_argument("--out", default="benchmarks/results/trace_r3")
    args = ap.parse_args()

    from scenery_insitu_tpu.utils.backend import enable_compile_cache

    enable_compile_cache()

    import jax

    from scenery_insitu_tpu.config import CompositeConfig, VDIConfig
    from scenery_insitu_tpu.core.camera import Camera
    from scenery_insitu_tpu.models.pipelines import grayscott_vdi_frame_step
    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.sim import grayscott as gs

    g = args.grid
    base = Camera.create((0.0, 0.6, 3.0), fov_y_deg=50.0, near=0.5,
                         far=20.0)
    step = grayscott_vdi_frame_step(
        1280, 720, sim_steps=args.sim_steps,
        vdi_cfg=VDIConfig(max_supersegments=args.k,
                          adaptive_mode="temporal"),
        comp_cfg=CompositeConfig(max_output_supersegments=args.k,
                                 adaptive_iters=2),
        engine="mxu", grid_shape=(g, g, g),
        axis_sign=slicer.choose_axis(base))
    frame = jax.jit(step)

    st = gs.GrayScott.init((g, g, g))
    u, v = st.u, st.v
    thr = jax.jit(step.init_threshold)(u, v, base.eye)
    for _ in range(3):                      # compile + reach steady state
        c, d, u, v, thr = frame(u, v, base.eye, thr)
    jax.block_until_ready(c)

    t0 = time.perf_counter()
    with jax.profiler.trace(args.out):
        for _ in range(args.frames):
            c, d, u, v, thr = frame(u, v, base.eye, thr)
        jax.block_until_ready(c)
    dt = (time.perf_counter() - t0) / args.frames

    files = glob.glob(os.path.join(args.out, "**", "*.xplane.pb"),
                      recursive=True)
    print(json.dumps({
        "metric": f"profiled_frame_{g}c",
        "value": round(dt * 1000.0, 2),
        "unit": "ms/frame",
        "platform": jax.devices()[0].platform,
        "trace_files": [os.path.relpath(f) for f in files],
        "frames": args.frames,
    }), flush=True)


if __name__ == "__main__":
    main()
