"""PSNR-vs-FLOPs-vs-ms ladder of the multi-resolution brick march
(LODConfig; docs/PERF.md "LOD marching"; ISSUE 16).

The scene is the LOD-shaped skewed scenario: dense NOISY content in the
near-camera z quarter (pinned fine by distance and by the TF-straddle
gate at its air boundary), exact-zero AIR in the next quarter (coarsens
to the admissible cap via ``lod.coarsen_empty``), and a SMOOTH visible
field in the far half (coarsens by the screen-space error bound, and
pooling a smooth field is nearly exact — this is where the PSNR cost
lives). The transfer function is the test ramp (0.05, 0.8) so the air
band is genuinely invisible and the content/air boundary bricks
straddle the 0.05 edge.

Each ladder rung is one ``lod.error_px`` budget: the REAL planner
(parallel.lod.select_levels, the exact function the session replan
calls) picks the level tuple from the live/range profiles + camera,
the distributed MXU brick step renders it on an 8-rank mesh (virtual
CPU devices or real chips), and the rung reports

  levels        the planner's tuple (histogram in the artifact)
  psnr_db       vs the level-0 frame (render_vdi_same_view decode)
  flop_reduction  modeled march FLOPs, level-0 / rung
                (parallel.lod.modeled_march_flops — the two resample
                matmuls per slice; the second keeps the FINE output
                grid, so a level-l brick is NOT 8^-l but ~2^-l on its
                dominant term: the model is honest about that)
  frame_ms      measured distributed frame time (march + composite)

``value`` is the best flop_reduction among rungs holding
``--psnr-floor`` (default 40 dB) — the committed CPU capture
(results/lod_ab_r16_cpu.json) gates >= 2x at >= 40 dB, and the CI lod
lane re-checks the committed artifact's claim. Infinite PSNR (a rung
that only coarsened air) is reported as the JSON string "inf".

KNOB_MATRIX below is the registry of every march-path config knob this
ladder (or a sibling bench named in the entry) covers; the SITPU-KNOB
lint rule (tools/lint/knobs.py) fails when a knob is added to
LODConfig / SliceMarchConfig without registering it here — an
unbenched march knob is an unmeasured regression surface.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the distributed A/B needs the rank mesh BEFORE jax initializes
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    if os.environ.get("SITPU_CPU") == "1" or not os.environ.get(
            "JAX_PLATFORMS", "").startswith("tpu"):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count="
            + os.environ.get("SITPU_BENCH_RANKS", "8")).strip()

import jax
import jax.numpy as jnp
import numpy as np

from scenery_insitu_tpu.config import (CompositeConfig, LODConfig,
                                       SliceMarchConfig, VDIConfig)
from scenery_insitu_tpu.core.camera import Camera
from scenery_insitu_tpu.core.transfer import TransferFunction, opacity_edges
from scenery_insitu_tpu.core.vdi import render_vdi_same_view
from scenery_insitu_tpu.ops import occupancy as occ
from scenery_insitu_tpu.ops import slicer
from scenery_insitu_tpu.parallel import bricks as bk
from scenery_insitu_tpu.parallel import lod as lodm
from scenery_insitu_tpu.parallel.mesh import make_mesh
from scenery_insitu_tpu.parallel.pipeline import (distributed_vdi_step_mxu,
                                                  shard_volume)

# Every march-path knob (LODConfig + SliceMarchConfig) and the ladder /
# sibling bench that measures it. Keys are config override paths; the
# SITPU-KNOB rule diffs this dict against the dataclass fields.
KNOB_MATRIX = {
    "lod.enabled": "the A/B itself: every rung vs the level-0 baseline",
    "lod.max_level": "ladder cap; rungs report the admissible clamp",
    "lod.error_px": "THE ladder axis: one rung per budget",
    "lod.coarsen_empty": "air-quarter rungs isolate the empty coarsen",
    "lod.live_eps": "sets the air/visible cut of coarsen_empty rungs",
    "lod.tf_edge_eps": "straddle-gate width; boundary bricks in every "
                       "rung's level histogram pin its effect",
    "lod.hysteresis": "replan damping — session-side; lod_bench plans "
                      "each rung cold (prev=None), the session A/B in "
                      "benchmarks/scenario_bench.py carries it",
    "slicer.engine": "mxu is the only coarse consumer (gather ledgers "
                     "lod.engine); render_bench.py A/Bs the engines",
    "slicer.scale": "virtual-grid multiplier; render_bench.py sweeps it",
    "slicer.chunk": "fold chunking; chipbench's fold_device_ms",
    "slicer.matmul_dtype": "bf16/f32 operand A/B in render_bench.py",
    "slicer.render_dtype": "marched-copy storage dtype; hbm_bench.py",
    "slicer.s_floor": "near-plane clip; fixed across rungs (geometry, "
                      "not cost) — render_bench.py owns it",
    "slicer.skip_empty": "empty-space skipping; occupancy_bench.py "
                         "(composes with LOD: a coarse brick still "
                         "chunk-skips)",
    "slicer.occupancy_vtiles": "in-plane skip tiles; occupancy_bench.py",
    "slicer.fold": "supersegment fold schedule; chipbench's "
                   "fold_device_ms",
}


def _t(fn, *args, iters=3, warmup=1):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters, out


def lod_field(grid: int) -> np.ndarray:
    """The LOD-shaped skewed scene (module docstring): far smooth half,
    exact-zero air quarter, near SPARSE noisy quarter. The near noise is
    sparse (~8% live, vortex-filament-like) so the far half stays
    genuinely visible through it — a solid near quarter occludes
    everything behind it and makes any far-coarsening PSNR vacuous."""
    rng = np.random.default_rng(16)
    data = np.zeros((grid, grid, grid), np.float32)
    z = np.arange(grid // 2)[:, None, None] / grid
    y = np.linspace(0, np.pi, grid)[None, :, None]
    x = np.linspace(0, np.pi, grid)[None, None, :]
    data[:grid // 2] = (0.3 + 0.12 * np.sin(4 * np.pi * z)
                        * np.sin(y) * np.sin(x)).astype(np.float32)
    lo = 3 * grid // 4
    shape = (grid - lo, grid, grid)
    mask = rng.random(shape) < 0.08
    data[lo:] = np.where(mask, 0.3 + 0.5 * rng.random(shape), 0.0
                         ).astype(np.float32)
    return data


def _psnr(a: np.ndarray, b: np.ndarray) -> float:
    mse = float(np.mean((np.asarray(a, np.float64)
                         - np.asarray(b, np.float64)) ** 2))
    return float("inf") if mse == 0.0 else 10.0 * np.log10(1.0 / mse)


def main(args):
    dev = jax.devices()[0]
    # a 1-chip machine clamps the rank mesh; the
    # brick count stays at the full ladder width so the level histogram
    # is comparable across captures
    n = min(args.ranks, len(jax.devices()))
    grid, nb = args.grid, args.bricks or max(16, 2 * n)
    field = jnp.asarray(lod_field(grid))
    tf = TransferFunction.ramp(0.05, 0.8, 0.7)
    # near the NOISY quarter (high z): distance separates far-smooth
    # from near-noisy by about one level octave
    cam = Camera.create((0.0, 0.6, 3.0), fov_y_deg=50.0, near=0.5,
                        far=20.0)
    march_cfg = SliceMarchConfig(
        matmul_dtype="f32" if dev.platform != "tpu" else "bf16",
        scale=args.scale)
    spec = slicer.make_spec(cam, (grid, grid, grid), march_cfg,
                            multiple_of=n)
    vdi_cfg = VDIConfig(max_supersegments=args.k, adaptive_iters=2)

    vox = 2.0 / grid
    origin = jnp.asarray([-1.0, -1.0, -1.0], jnp.float32)
    spacing = jnp.full((3,), vox, jnp.float32)
    mesh = make_mesh(n)
    sdata = shard_volume(field, mesh)

    # the planner's inputs, exactly as the session replan fetches them
    live = lodm.per_brick(np.asarray(occ.z_live_profile(field, tf,
                                                        nzb=nb)), nb)
    lo_p, hi_p = occ.z_range_profile(field, nzb=nb)
    lo_p, hi_p = np.asarray(lo_p), np.asarray(hi_p)
    edges = opacity_edges(tf)
    dims = (grid, grid, grid)
    plan_kw = dict(dims=dims, origin=np.asarray(origin),
                   spacing=np.asarray(spacing),
                   eye=np.asarray(cam.eye), fov_y=float(cam.fov_y),
                   height_px=spec.nj)

    base_map = bk.BrickMap.contiguous(grid, n, nb)
    base_flops = lodm.modeled_march_flops((0,) * nb, dims, spec.ni,
                                          spec.nj)

    def render(levels):
        bm = base_map.with_levels(levels)
        step = distributed_vdi_step_mxu(
            mesh, tf, spec, vdi_cfg,
            CompositeConfig(max_output_supersegments=2 * args.k,
                            adaptive_iters=2, rebalance="bricks"),
            bricks=bm)
        dt, (vdi, _) = _t(lambda: step(sdata, origin, spacing, cam),
                          iters=args.iters)
        return dt * 1e3, np.asarray(render_vdi_same_view(vdi))

    ms0, img0 = render((0,) * nb)
    ladder = [{"error_px": None, "levels": [0] * nb, "psnr_db": "inf",
               "flop_reduction": 1.0, "frame_ms": round(ms0, 2),
               "note": "level-0 baseline (bitwise the pre-LOD path)"}]
    for err_px in args.ladder:
        cfg = LODConfig(enabled=True, max_level=args.max_level,
                        error_px=err_px, live_eps=args.live_eps)
        levels = lodm.select_levels(live, lo_p, hi_p, edges, cfg=cfg,
                                    **plan_kw)
        ms, img = render(levels)
        psnr = _psnr(img0, img)
        flops = lodm.modeled_march_flops(levels, dims, spec.ni, spec.nj)
        ladder.append({
            "error_px": err_px,
            "levels": list(levels),
            "level_hist": {str(l): int(sum(1 for x in levels if x == l))
                           for l in sorted(set(levels))},
            "psnr_db": "inf" if psnr == float("inf") else round(psnr, 2),
            "flop_reduction": round(base_flops / flops, 3),
            "frame_ms": round(ms, 2),
            "march_speedup": round(ms0 / ms, 3),
        })

    def _admissible(r):
        return r["psnr_db"] == "inf" or r["psnr_db"] >= args.psnr_floor

    good = [r for r in ladder[1:] if _admissible(r)]
    best = max(good, key=lambda r: r["flop_reduction"]) if good else None
    out = {
        "metric": f"lod_ladder_{grid}c_{n}ranks_{dev.platform}",
        "unit": "modeled march FLOP reduction at the PSNR floor "
                "(level-0 / best admissible rung)",
        "value": best["flop_reduction"] if best else 0.0,
        "psnr_db": best["psnr_db"] if best else None,
        "psnr_floor_db": args.psnr_floor,
        "best_error_px": best["error_px"] if best else None,
        "ladder": ladder,
        "scene": {"grid": grid, "layout": "far smooth half / zero air "
                  "quarter / near noisy quarter", "nbricks": nb,
                  "brick_depth": grid // nb,
                  "tf_edges": [round(float(e), 4) for e in edges]},
        "config": {"ranks": n, "k": args.k, "nbricks": nb,
                   "max_level": args.max_level, "live_eps": args.live_eps,
                   "image": [spec.ni, spec.nj], "fold": spec.fold,
                   "iters": args.iters, "platform": dev.platform,
                   "device": dev.device_kind},
        "note": ("levels chosen by parallel.lod.select_levels from the "
                 "real live/range profiles (the session replan path); "
                 "frames rendered by the distributed MXU brick step on "
                 f"{n} ranks; FLOPs modeled per parallel.lod"
                 ".modeled_march_flops. frame_ms at toy grids is "
                 "dominated by per-brick fixed cost (thresholds, fold "
                 "state, compile-shaped dispatch), so CPU march_speedup "
                 "< 1 here is expected — the FLOP model is the claim "
                 "that transfers to 2048^3+ (see "
                 "modeled_projection.py --lod)"),
    }
    print(json.dumps(out), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--grid", type=int,
                    default=int(os.environ.get("SITPU_BENCH_GRID", "64")))
    ap.add_argument("--ranks", type=int,
                    default=int(os.environ.get("SITPU_BENCH_RANKS", "8")))
    ap.add_argument("--bricks", type=int, default=0,
                    help="brick count (0 = 2 per rank)")
    ap.add_argument("--k", type=int,
                    default=int(os.environ.get("SITPU_BENCH_K", "8")))
    ap.add_argument("--ladder", type=float, nargs="+",
                    default=[1.5, 3.0, 6.0, 12.0],
                    help="lod.error_px budgets, one rung each")
    ap.add_argument("--max-level", type=int, default=2)
    ap.add_argument("--live-eps", type=float, default=1e-3)
    ap.add_argument("--scale", type=float, default=2.0)
    ap.add_argument("--psnr-floor", type=float, default=40.0)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--out", default=None)
    cli = ap.parse_args()
    if os.environ.get("SITPU_CPU") == "1":
        from scenery_insitu_tpu.utils.backend import pin_cpu_backend
        pin_cpu_backend()
    from scenery_insitu_tpu.utils.backend import enable_compile_cache
    enable_compile_cache()
    main(cli)
