"""Per-rank cost of BASELINE Config 2 (the PRIMARY metric's own terms).

BASELINE.md defines the primary metric as "Gray-Scott 512^3 FPS +
VDI-composite ms/frame" on **v5e-8** — an 8-rank sort-last pipeline
(Config 2), where each chip sims and marches a D/8 z-slab and
composites one W/8 output strip. Every committed flagship number so far
measured the WHOLE 512^3 volume on ONE chip, i.e. 8x the per-rank march
work the metric actually asks one chip to do.

On one chip this harness
measures the real per-rank constituents and models the one part
that needs 8 chips (the ICI all_to_all), with the assumption printed:

  sim_slab    10 Gray-Scott steps of the [D/n, H, W] slab
              (multi_step_fast — the production path; the ~4 MB/step
              halo exchange the real pipeline overlaps is noted, not
              modeled)
  march_slab  one temporal write march of the slab through the real
              distributed geometry (shifted origin + global clip box,
              exactly what _mxu_rank_generate runs per rank), VDI on
              the full virtual pixel grid
  composite   composite_vdis over n rank-VDI column strips ([n, K, 4,
              Nj, Ni/n] — the real shapes; contents replicated, cost
              identical)
  a2a_model   per-chip egress (n-1)/n of the VDI bytes at an ASSUMED
              ICI effective bandwidth (default 45 GB/s per chip,
              overridable via SITPU_A2A_GBPS)

Prints ONE JSON line with the pieces and two projections:
projected_fps_v5e8 (sim + march + a2a + composite) and
projected_render_fps_v5e8 (in-situ split: sim feeds from elsewhere).

--rebalance both|even|occupancy (ISSUE 10; docs/PERF.md "Render
rebalancing") switches the harness to the render-rebalancing A/B: on a
SKEWED scene (live work concentrated low-z, >=4x live-fraction spread
across rank bands) it measures every rank's band-march time under the
even z-slab split and under the occupancy plan
(ops/occupancy.slice_plan on the z live profile; planned bands padded
to max(plan) exactly like mesh.reslab_z pads them), and reports the
straggler factor (max/mean per-rank march ms) of each — the frame
barrier is the MAX over ranks, so the straggler reduction is the frame
speedup the rebalance buys. One chip marches the bands serially
(band contents and shapes are exactly the distributed ones; only
concurrency is serialized), so the per-rank times are the real
constituents. ``--out`` writes the JSON artifact
(rebalance_ab_r10_cpu.json is the committed CPU capture).

--rebalance bricks|all additionally measures the NON-CONVEX brick map
(ISSUE 15; docs/SCENARIOS.md "Brick maps"): the steal planner
(parallel.bricks.steal_plan) is converged on the scene's per-brick
live work and each rank's time is the SUM of its per-brick marches —
contiguity gone, min-depth/max-depth padding gone, so the dense region
spreads one brick per rank (bricks_ab_r15_cpu.json is the committed
CPU capture: even 2.90 -> slabs 1.82 -> bricks 1.08 straggler).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from scenery_insitu_tpu.config import SliceMarchConfig, VDIConfig, \
    CompositeConfig
from scenery_insitu_tpu.core.camera import Camera
from scenery_insitu_tpu.core.volume import Volume
from scenery_insitu_tpu.ops import slicer
from scenery_insitu_tpu.ops.composite import composite_vdis
from scenery_insitu_tpu.core.transfer import for_dataset
from scenery_insitu_tpu.sim import grayscott as gs


def _t(fn, *args, iters=5, warmup=1):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters, out


def _skewed_field(grid: int) -> "jnp.ndarray":
    """Deterministic skewed scene: dense content in the low QUARTER of z
    only — under the even 8-rank split, ranks 0-1 march solid live
    chunks while ranks 2-7 march air (live-fraction spread >> 4x), the
    regime ROADMAP item 3 left open (PR 6 measured live-cell 0.41 at
    512^3 with exactly this kind of banding)."""
    import numpy as np

    data = np.zeros((grid, grid, grid), np.float32)
    rng = np.random.default_rng(7)
    lo, hi = 1, grid // 4
    data[lo:hi] = (0.3 + 0.5 * rng.random((hi - lo, grid, grid))
                   ).astype(np.float32)
    return jnp.asarray(data)


def rebalance_ab(args):
    """Per-rank march-time A/B: even z-slab split vs the occupancy
    plan, straggler factor (max/mean) each — the frame-barrier term."""
    import numpy as np

    from scenery_insitu_tpu.core.transfer import for_dataset
    from scenery_insitu_tpu.ops import occupancy as occ

    dev = jax.devices()[0]
    grid = args.grid
    n = args.ranks
    field = _skewed_field(grid)
    tf = for_dataset("gray_scott")
    cam = Camera.create((0.0, 0.6, 3.0), fov_y_deg=50.0, near=0.5,
                        far=20.0)
    # the march's cost granularity IS the fold chunk: a band holding 1
    # live slice still pays its whole chunk of resampling matmuls, so
    # the plan quantum and the chunk must agree or the planned bands
    # round up to chunk-sized work anyway (docs/PERF.md "Render
    # rebalancing" — the production default ties rebalance_quantum=4 to
    # chunked skipping the same way)
    march_cfg = SliceMarchConfig(fold=args.fold,
                                 chunk=max(4, args.quantum),
                                 matmul_dtype="f32" if
                                 dev.platform != "tpu" else "bf16")
    vdi_cfg = VDIConfig(max_supersegments=args.k, adaptive_iters=2,
                        adaptive_mode="histogram")
    spec = slicer.make_spec(cam, (grid, grid, grid), march_cfg,
                            multiple_of=n)

    spacing = 2.0 / grid
    origin = jnp.array([-1.0, -1.0, -1.0], jnp.float32)
    spc = jnp.array([spacing] * 3, jnp.float32)
    gmax = origin + jnp.array([grid] * 3, jnp.float32) * spc

    prof = np.asarray(occ.z_live_profile(field, tf))
    even = occ.even_plan(grid, n)
    plan = occ.slice_plan(prof, grid, n, min_depth=args.min_depth,
                          quantum=args.quantum)
    band_live = [float(w) for w in occ.plan_work(prof, grid, even,
                                                 base_cost=0.0)]
    spread = (max(band_live) / max(min(band_live), 1e-9)
              if min(band_live) > 0 else float("inf"))

    def march_band(g0: int, depth: int, pad_to: int):
        """Time one rank's band march through the REAL distributed
        geometry: band volume (zero-padded to the plan max like
        mesh.reslab_z pads it), shifted origin, global box, w_bounds
        ownership."""
        band = np.zeros((pad_to, grid, grid), np.float32)
        band[:depth] = np.asarray(field[g0:g0 + depth])
        l_origin = origin.at[2].add(g0 * spacing)
        z_lo = origin[2] + g0 * spacing
        z_hi = origin[2] + (g0 + depth) * spacing

        @jax.jit
        def march(data):
            vol = Volume(data, l_origin, spc)
            vdi, _, _ = slicer.generate_vdi_mxu(
                vol, tf, cam, spec, vdi_cfg, box_min=origin, box_max=gmax,
                w_bounds=(z_lo, z_hi))
            return vdi.color, vdi.depth

        dt, _ = _t(march, jnp.asarray(band), iters=args.iters)
        return dt * 1e3

    def mode_times(p):
        pad_to = max(p)
        starts = np.concatenate([[0], np.cumsum(p)])[:n]
        return [march_band(int(starts[r]), int(p[r]), int(pad_to))
                for r in range(n)]

    def brick_times(bmap):
        """Per-rank march time under a brick map = the SUM of the
        rank's per-brick marches (the real brick path marches each
        slot separately; serialized here like the band A/B — band
        contents, bounds and shapes are exactly the distributed
        ones)."""
        bz = bmap.brick_depth
        out_ms = []
        for r in range(n):
            ms = 0.0
            for z0, _ in bmap.intervals(r):
                ms += march_band(z0, bz, bz)
            out_ms.append(ms)
        return out_ms

    # brick-stealing map (ISSUE 15; docs/SCENARIOS.md "Brick maps"):
    # converge the session's move-capped steal loop up front — the bench
    # measures the steady-state assignment the replans settle on
    from scenery_insitu_tpu.parallel import bricks as bk

    nb = getattr(args, "bricks", 0) or bk.auto_nbricks(grid, n)
    bwork = bk.brick_work(prof, grid, nb)
    bmap = bk.BrickMap.contiguous(grid, n, nb)
    for _ in range(4 * nb):
        nxt = bk.steal_plan(bmap, bwork, max_moves=4, hysteresis=0.05)
        if nxt is bmap:
            break
        bmap = nxt

    run_modes = {"both": ("even", "occupancy"),
                 "all": ("even", "occupancy", "bricks"),
                 "bricks": ("even", "bricks"),
                 "even": ("even",), "occupancy": ("occupancy",)}[
                     args.rebalance]
    out = {"metric": f"rebalance_ab_{grid}c_{n}ranks_{dev.platform}",
           "unit": "straggler factor reduction (max/mean per-rank march"
                   " ms, even / rebalanced)",
           "scene": {"grid": grid,
                     "band_live_spread": round(spread, 2),
                     "z_profile_bins": len(prof)},
           "plan": list(plan),
           "bricks_map": {"nbricks": nb, "brick_depth": grid // nb,
                          "owner": list(bmap.owner),
                          "slots": bmap.slots},
           "modeled": {
               "straggler_even": round(
                   occ.straggler_factor(prof, grid, even), 3),
               "straggler_planned": round(
                   occ.straggler_factor(prof, grid, plan), 3),
               "straggler_bricks": round(
                   bk.straggler_factor(bmap, bwork), 3)},
           "config": {"ranks": n, "k": args.k, "fold": spec.fold,
                      "image": [spec.ni, spec.nj],
                      "min_depth": args.min_depth,
                      "quantum": args.quantum, "iters": args.iters,
                      "platform": dev.platform,
                      "device": dev.device_kind}}
    for mode in ("even", "occupancy", "bricks"):
        if mode not in run_modes:
            continue
        if mode == "bricks":
            ms = brick_times(bmap)
        else:
            ms = mode_times(even if mode == "even" else plan)
        out[mode] = {
            "per_rank_march_ms": [round(m, 2) for m in ms],
            "max_ms": round(max(ms), 2),
            "mean_ms": round(float(np.mean(ms)), 2),
            "straggler_factor": round(max(ms) / float(np.mean(ms)), 3),
        }
    if "even" in out and "occupancy" in out:
        out["value"] = round(out["even"]["straggler_factor"]
                             / out["occupancy"]["straggler_factor"], 3)
        out["frame_march_speedup"] = round(
            out["even"]["max_ms"] / out["occupancy"]["max_ms"], 3)
    if "even" in out and "bricks" in out:
        out["value_bricks"] = round(out["even"]["straggler_factor"]
                                    / out["bricks"]["straggler_factor"],
                                    3)
        out["frame_march_speedup_bricks"] = round(
            out["even"]["max_ms"] / out["bricks"]["max_ms"], 3)
    print(json.dumps(out), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)


def main():
    if os.environ.get("SITPU_CPU") == "1":
        from scenery_insitu_tpu.utils.backend import pin_cpu_backend
        pin_cpu_backend()
    from scenery_insitu_tpu.utils.backend import enable_compile_cache
    enable_compile_cache()
    dev = jax.devices()[0]
    grid = int(os.environ.get("SITPU_BENCH_GRID", "512"))
    n = int(os.environ.get("SITPU_BENCH_RANKS", "8"))
    k = int(os.environ.get("SITPU_BENCH_K", "16"))
    sim_steps = int(os.environ.get("SITPU_BENCH_SIM_STEPS", "10"))
    a2a_gbps = float(os.environ.get("SITPU_A2A_GBPS", "45"))
    fold = os.environ.get("SITPU_BENCH_FOLD", "auto")

    d_loc = grid // n
    cam = Camera.create((0.0, 0.6, 3.0), fov_y_deg=50.0, near=0.5, far=20.0)
    march_cfg = SliceMarchConfig(fold=fold, chunk=min(16, d_loc))
    vdi_cfg = VDIConfig(max_supersegments=k, adaptive_mode="temporal")
    comp_cfg = CompositeConfig(max_output_supersegments=k)
    tf = for_dataset("gray_scott")

    # ---- per-rank slab state: middle slab of a developed global field
    st = gs.GrayScott.init((grid, grid, grid))
    st = jax.jit(lambda s: gs.multi_step(s, 5))(st)
    r0 = (n // 2) * d_loc
    slab_u = st.u[r0:r0 + d_loc]
    slab_v = st.v[r0:r0 + d_loc]
    slab = gs.GrayScott(slab_u, slab_v, st.params)

    # ---- sim of one slab (the production fast path)
    sim_fn = jax.jit(lambda s: gs.multi_step_fast(s, sim_steps))
    t_sim, _ = _t(sim_fn, slab, iters=3)

    # ---- per-rank march: the distributed geometry (shifted origin,
    # global clip box), exactly what _mxu_rank_generate does per rank
    # (parallel/pipeline.py), VDI on the full virtual pixel grid
    spacing = 2.0 / grid
    g_origin = jnp.array([-1.0 + 0.5 * spacing] * 3, jnp.float32)
    l_origin = g_origin.at[2].add(r0 * spacing)   # z slab offset (D axis)
    vol = Volume.create(slab_v, origin=l_origin,
                        spacing=jnp.array([spacing] * 3, jnp.float32))
    spec = slicer.make_spec(cam, (grid, grid, grid), march_cfg)
    box_min = g_origin - 0.5 * spacing
    box_max = box_min + 2.0

    thr = slicer.initial_threshold(vol, tf, cam, spec, vdi_cfg,
                                   box_min=box_min, box_max=box_max)

    @jax.jit
    def march(vol_data, thr):
        v2 = Volume(vol_data, vol.origin, vol.spacing)
        vdi, meta, axcam, thr2 = slicer.generate_vdi_mxu_temporal(
            v2, tf, cam, spec, thr, vdi_cfg, box_min=box_min,
            box_max=box_max)
        return vdi.color, vdi.depth, thr2

    t_march, (color, depth, _) = _t(march, vol.data, thr, iters=5)

    # ---- composite over n rank strips (real shapes, replicated content)
    ni = spec.ni
    strip = ni // n
    colors = jnp.stack([color[..., :strip]] * n)   # [n, K, 4, Nj, Ni/n]
    depths = jnp.stack([depth[..., :strip]] * n)

    @jax.jit
    def comp(colors, depths):
        out = composite_vdis(colors, depths, comp_cfg)
        return out.color, out.depth

    t_comp, _ = _t(comp, colors, depths, iters=5)

    # ---- modeled ICI all_to_all: per-chip egress of (n-1)/n VDI bytes
    vdi_bytes = (color.size + depth.size) * 4
    a2a_bytes = vdi_bytes * (n - 1) / n
    t_a2a = a2a_bytes / (a2a_gbps * 1e9)

    total = t_sim + t_march + t_a2a + t_comp
    render = t_march + t_a2a + t_comp
    print(json.dumps({
        "metric": f"config2_per_rank_{grid}c_{n}ranks_projection",
        "value": round(1.0 / total, 3),
        "unit": "frames/s (projected v5e-8, a2a modeled)",
        "per_rank_sim_ms": round(t_sim * 1e3, 2),
        "per_rank_march_ms": round(t_march * 1e3, 2),
        "composite_ms": round(t_comp * 1e3, 2),
        "a2a_model_ms": round(t_a2a * 1e3, 3),
        "a2a_assumed_gbps": a2a_gbps,
        "a2a_bytes": round(a2a_bytes),
        "projected_fps_v5e8": round(1.0 / total, 3),
        "projected_render_fps_v5e8": round(1.0 / render, 3),
        "note": ("per-rank sim+march+composite MEASURED on one chip with "
                 "the real distributed slab geometry and shapes; ICI "
                 "all_to_all modeled at the stated bandwidth; sim halo "
                 "exchange (~4 MB/step) not modeled"),
        "config": {"grid": grid, "ranks": n, "k": k,
                   "sim_steps": sim_steps, "fold": spec.fold,
                   "image": [spec.ni, spec.nj], "chunk": march_cfg.chunk,
                   "platform": dev.platform, "device": dev.device_kind},
    }), flush=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--rebalance",
                    choices=("both", "all", "even", "occupancy",
                             "bricks"),
                    default=None,
                    help="run the render-rebalancing A/B instead of the "
                         "legacy Config-2 projection ('bricks' = even "
                         "vs the brick-stealing map, 'all' = all three)")
    ap.add_argument("--bricks", type=int, default=0,
                    help="brick count of the --rebalance bricks mode "
                         "(0 = auto_nbricks)")
    ap.add_argument("--grid", type=int,
                    default=int(os.environ.get("SITPU_BENCH_GRID",
                                               "64")))
    ap.add_argument("--ranks", type=int,
                    default=int(os.environ.get("SITPU_BENCH_RANKS", "8")))
    ap.add_argument("--k", type=int,
                    default=int(os.environ.get("SITPU_BENCH_K", "8")))
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--min-depth", type=int, default=2)
    ap.add_argument("--quantum", type=int, default=4)
    ap.add_argument("--fold",
                    default=os.environ.get("SITPU_BENCH_FOLD", "auto"))
    ap.add_argument("--out", default=None)
    cli = ap.parse_args()
    if cli.rebalance is not None:
        if os.environ.get("SITPU_CPU") == "1":
            from scenery_insitu_tpu.utils.backend import pin_cpu_backend
            pin_cpu_backend()
        from scenery_insitu_tpu.utils.backend import enable_compile_cache
        enable_compile_cache()
        rebalance_ab(cli)
    else:
        main()
