"""Distributed per-phase breakdown (VERDICT weak #6): the production frame
is ONE jitted SPMD program (by design — XLA overlaps generate, all_to_all,
composite), so the session's timers can only see dispatch+fetch. This
diagnostic splits the chain into separately-jitted stages with
block_until_ready between them — the TPU analog of the reference's
per-phase timer taxonomy (total / all_to_all / composite / gather,
DistributedVolumeRenderer.kt:622-648). The split forces materialization
between stages, so the SUM here is an upper bound on the fused frame time
(also printed for comparison).

Inputs are chained across iterations so no execution-dedup layer can fake
the timings. Runs on the virtual CPU mesh by default; SITPU_BENCH_REAL=1
uses real devices.

Usage: python benchmarks/phase_bench.py [--ranks 8] [--grid 64] [--iters 5]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_CHILD = "_SITPU_PHASEBENCH_CHILD"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ranks", type=int, default=8)
    ap.add_argument("--grid", type=int, default=64)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--sim-steps", type=int, default=5)
    # HBM-traffic lever A/Bs (ISSUE 1): bf16 marched-volume copy and
    # time-fused sim stencil
    ap.add_argument("--render-dtype", choices=("f32", "bf16"),
                    default="f32")
    ap.add_argument("--sim-fused", type=int, default=0)
    # fleet-telemetry overhead guard (ISSUE 17): A/B the per-frame cost
    # of the obs plane (span + lineage + SLO observe) and fail if the
    # enabled path costs more than --obs-budget over the disabled one
    ap.add_argument("--obs-guard", action="store_true")
    ap.add_argument("--obs-budget", type=float, default=0.02)
    args = ap.parse_args()
    n = args.ranks

    from scenery_insitu_tpu.utils.backend import (pin_cpu_backend,
                                                  reexec_virtual_mesh)

    if os.environ.get(_CHILD) != "1" and os.environ.get(
            "SITPU_BENCH_REAL") != "1":
        reexec_virtual_mesh(n, _CHILD)

    import jax

    from jax import shard_map

    if os.environ.get(_CHILD) == "1":
        pin_cpu_backend()

    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from scenery_insitu_tpu.config import (CompositeConfig, SliceMarchConfig,
                                           VDIConfig)
    from scenery_insitu_tpu.core.camera import Camera
    from scenery_insitu_tpu.core.transfer import for_dataset
    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.ops.composite import composite_vdis
    from scenery_insitu_tpu.parallel.mesh import make_mesh
    from scenery_insitu_tpu.parallel.pipeline import (_exchange_columns,
                                                      _mxu_rank_generate,
                                                      distributed_vdi_step_mxu,
                                                      shard_volume)
    from scenery_insitu_tpu.sim import grayscott as gs

    mesh = make_mesh(n)
    axis = mesh.axis_names[0]
    g = args.grid
    tf = for_dataset("gray_scott")
    cam = Camera.create((0.0, 0.5, 3.0), fov_y_deg=50.0, near=0.5, far=20.0)
    vdi_cfg = VDIConfig(max_supersegments=args.k, adaptive_iters=2)
    comp_cfg = CompositeConfig(max_output_supersegments=args.k,
                               adaptive_iters=2)
    mcfg = SliceMarchConfig(
        matmul_dtype="f32" if jax.default_backend() != "tpu" else "bf16",
        render_dtype=args.render_dtype)
    spec = slicer.make_spec(cam, (g, g, g), mcfg, multiple_of=n)

    origin = jnp.array([-1.0, -1.0, -1.0], jnp.float32)
    spacing = jnp.full((3,), 2.0 / g, jnp.float32)

    # --------------------------------------------------- split-stage fns
    from scenery_insitu_tpu import obs

    sim_fused = bool(args.sim_fused)
    if sim_fused and n > 1:
        # the fused Pallas stencil's periodic wrap is per-buffer, so it
        # cannot run on z-sharded state (sim/pallas_stencil.py) — the
        # multi-rank sim lever is the roll path, as in the session
        print("[phase_bench] --sim-fused needs a 1-rank mesh (the Pallas "
              "stencil is not partitionable); using the roll path",
              file=sys.stderr)
        obs.degrade("phase_bench.sim_fused", "pallas", "xla_roll",
                    "fused stencil needs a 1-rank mesh (periodic wrap "
                    "is per-buffer)", warn=False)
        sim_fused = False
    advance = gs.multi_step_fast if sim_fused else gs.multi_step
    sim_fn = jax.jit(lambda u, v: advance(
        gs.GrayScott(u, v, gs.GrayScottParams.create()), args.sim_steps))

    def gen(local, o, s, c):
        # (vdi, meta, axcam, thr', reuse') since the temporal-delta PR
        vdi, meta, *_ = _mxu_rank_generate(local, o, s, c, slicer, spec,
                                           tf, vdi_cfg, axis, n)
        return vdi.color, vdi.depth

    gen_fn = jax.jit(shard_map(
        gen, mesh=mesh, in_specs=(P(axis, None, None), P(), P(), P()),
        out_specs=(P(axis), P(axis)), check_vma=False))

    def exch(color, depth):
        return (_exchange_columns(color, n, axis),
                _exchange_columns(depth, n, axis))

    exch_fn = jax.jit(shard_map(
        exch, mesh=mesh, in_specs=(P(axis), P(axis)),
        out_specs=(P(axis), P(axis)), check_vma=False))

    def comp(colors, depths):
        out = composite_vdis(colors, depths, comp_cfg)
        return out.color, out.depth

    comp_fn = jax.jit(shard_map(
        comp, mesh=mesh, in_specs=(P(axis), P(axis)),
        out_specs=(P(None, None, None, axis), P(None, None, None, axis)),
        check_vma=False))

    fused = distributed_vdi_step_mxu(mesh, tf, spec, vdi_cfg, comp_cfg)

    st = gs.GrayScott.init((g, g, g))
    u = shard_volume(st.u, mesh)
    v = shard_volume(st.v, mesh)

    phases = {k: 0.0 for k in
              ("sim", "generate", "all_to_all", "composite", "gather",
               "fused_total")}

    def tick(key, fn, *a):
        t0 = time.perf_counter()
        out = fn(*a)
        jax.block_until_ready(out)
        phases[key] += time.perf_counter() - t0
        return out

    # warm up every stage
    stw = sim_fn(u, v)
    cw, dw = gen_fn(stw.v, origin, spacing, cam)
    ce, de = exch_fn(cw, dw)
    comp_out = comp_fn(ce, de)
    fused_out = fused(stw.v, origin, spacing, cam)
    jax.block_until_ready((comp_out, fused_out))

    for it in range(args.iters):
        stp = tick("sim", sim_fn, u, v)
        u, v = stp.u, stp.v
        c, d = tick("generate", gen_fn, v, origin, spacing, cam)
        ce, de = tick("all_to_all", exch_fn, c, d)
        oc, od = tick("composite", comp_fn, ce, de)
        t0 = time.perf_counter()
        host = (jnp.asarray(oc).block_until_ready()
                if hasattr(oc, "block_until_ready") else oc)
        import numpy as _np
        _np.asarray(host)
        phases["gather"] += time.perf_counter() - t0
        vdi_f, _ = tick("fused_total", fused, v, origin, spacing, cam)

    ms = {k: round(t / args.iters * 1000, 2) for k, t in phases.items()}

    # obs plane A/B: the identical warm fused frame, once under a
    # disabled Recorder and once under an enabled one doing everything
    # Session.run does per frame (span + lineage instant + SLO observe).
    # The fleet-obs CI lane gates overhead_frac at --obs-budget (2%).
    from scenery_insitu_tpu.config import SLOConfig
    from scenery_insitu_tpu.obs.collector import lineage
    from scenery_insitu_tpu.obs.slo import SLOEngine

    obs_ab = {}
    saved_rec = obs.get_recorder()
    for mode in (False, True):
        rec = obs.Recorder(enabled=mode)
        obs.set_recorder(rec)
        slo = SLOEngine(SLOConfig(enabled=mode, frame_p99_ms=1e9), rec)
        t0 = time.perf_counter()
        for it in range(args.iters):
            t_f = time.perf_counter()
            with rec.span("frame", frame=it):
                out = fused(v, origin, spacing, cam)
                jax.block_until_ready(out[0].color)
            lineage("publish", "send", it)
            slo.observe("frame_ms", (time.perf_counter() - t_f) * 1e3,
                        frame=it)
        obs_ab["enabled_ms" if mode else "disabled_ms"] = round(
            (time.perf_counter() - t0) / args.iters * 1000, 2)
    obs.set_recorder(saved_rec)
    obs_ab["overhead_frac"] = round(
        obs_ab["enabled_ms"] / max(obs_ab["disabled_ms"], 1e-9) - 1.0, 4)

    # the fused step covers generate+all_to_all+composite ONLY (sim runs
    # before it, gather after) — compare like with like
    split_render = sum(ms[k] for k in ("generate", "all_to_all", "composite"))
    from scenery_insitu_tpu.obs.device import device_cost

    print(json.dumps({
        "metric": f"phase_breakdown_{n}ranks_{g}c",
        "unit": "ms/frame",
        "phases": ms,
        "split_render_ms": round(split_render, 2),
        "fused_render_ms": ms["fused_total"],
        "overlap_gain": round(split_render / max(ms["fused_total"], 1e-9), 2),
        "levers": {"render_dtype": args.render_dtype,
                   # EFFECTIVE (multi-rank downgrades to roll)
                   "sim_fused": sim_fused},
        "obs_overhead": obs_ab,
        # device-cost truth + everything that did not run as configured
        # (same record shape bench.py embeds — see docs/OBSERVABILITY.md)
        "cost_analysis": {"fused_step": device_cost(
            fused, v, origin, spacing, cam)},
        "degradations": obs.ledger(),
        "backend": jax.default_backend(),
    }))

    if args.obs_guard and obs_ab["overhead_frac"] > args.obs_budget:
        print(f"[phase_bench] obs overhead {obs_ab['overhead_frac']:.2%} "
              f"exceeds budget {args.obs_budget:.0%}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
