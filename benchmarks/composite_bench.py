"""Distributed compositing benchmark — replay stored VDI fixtures through
the real distribute/composite path (≅ VDICompositingTest.kt:207-330, the
reference's C++-driven MPI compositing benchmark).

The reference replays stored VDI dumps through ``distributeVDIsForBenchmark``
(plain MPI all-to-all) or ``distributeVDIsWithVariableLength`` (per-segment
LZ4 + alltoallv, :251-304), composites on the GPU, and emits machine-
greppable ``#COMP:rank:iter:sec#`` / ``#DECOM:rank:iter:sec#`` / ``#IT:...#``
markers (:301,336,397-398). This harness does the same on the TPU path:

- **ici mode** (default): per-rank sub-VDIs are placed rank-sharded on the
  device mesh and each iteration runs the one jitted SPMD step — width-axis
  column exchange + fused sort-merge composite — exactly the production
  pipeline's chain. ``--exchange both`` (the default) A/Bs the
  ``all_to_all`` schedule against the ring-pipelined one
  (CompositeConfig.exchange; docs/PERF.md "Exchange modes"), reporting
  per-mode ms/iter, the modeled exchange + composite working-set bytes
  (the N·K → ring_slots+K reduction) and output parity. ``--wire all``
  additionally A/Bs the supersegment wire formats (CompositeConfig.wire;
  docs/PERF.md "Wire formats"): each lossy mode reports ms/iter, the
  modeled per-wire exchange bytes, the XLA-cost-analysis bytes of the
  compiled step, and a PSNR block against the same-schedule f32 output.
- **compressed mode** (``--compressed``): the host hop — each rank's VDI is
  split into per-destination column segments, compressed (zstd by default),
  "exchanged", decompressed (timed as #DECOM) and composited (#COMP) — the
  variable-length-collective wire format of io.vdi_io.pack_vdi_segments.

Fixtures: ``--save-fixtures DIR`` writes per-rank sub-VDI .npz dumps from a
procedural volume (the fake-sim fixture strategy, SURVEY.md §4.3);
``--dir DIR`` replays existing dumps. Without either, fixtures are built
in-memory.

Runs on the virtual CPU mesh by default (set SITPU_BENCH_REAL=1 to use real
devices when you have >= n of them). Prints markers + one JSON summary.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_CHILD = "_SITPU_COMPBENCH_CHILD"


from scenery_insitu_tpu.utils.backend import (pin_cpu_backend,  # noqa: E402
                                              reexec_virtual_mesh)


def build_fixtures(n: int, grid: int, width: int, height: int, k: int,
                   max_steps: int):
    """Per-rank sub-VDIs: each rank raycasts its z-slab of a procedural
    volume, clipped half-open — the same decomposition the pipeline uses."""
    import jax.numpy as jnp

    from scenery_insitu_tpu.config import VDIConfig
    from scenery_insitu_tpu.core.camera import Camera
    from scenery_insitu_tpu.core.transfer import for_dataset
    from scenery_insitu_tpu.core.volume import procedural_volume
    from scenery_insitu_tpu.ops.vdi_gen import generate_vdi

    vol = procedural_volume(grid, kind="blobs", seed=11)
    tf = for_dataset("procedural")
    cam = Camera.create((0.2, 0.5, 2.9), fov_y_deg=45.0, near=0.3, far=10.0)
    cfg = VDIConfig(max_supersegments=k, adaptive_iters=2)
    d = grid
    dz = float(vol.spacing[2])
    vdis, metas = [], []
    for r in range(n):
        z0 = float(vol.origin[2]) + r * (d // n) * dz
        z1 = float(vol.origin[2]) + (r + 1) * (d // n) * dz
        cmin = jnp.asarray([vol.world_min[0], vol.world_min[1], z0])
        cmax = jnp.asarray([vol.world_max[0], vol.world_max[1], z1])
        vdi, meta = generate_vdi(vol, tf, cam, width, height, cfg,
                                 max_steps=max_steps,
                                 clip_min=cmin, clip_max=cmax)
        vdis.append(vdi)
        metas.append(meta)
    return vdis, metas


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ranks", type=int, default=8)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--grid", type=int, default=64)
    ap.add_argument("--width", type=int, default=256)
    ap.add_argument("--height", type=int, default=144)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--k-out", type=int, default=8)
    ap.add_argument("--max-steps", type=int, default=96)
    ap.add_argument("--compressed", action="store_true",
                    help="host-hop per-segment compression variant")
    ap.add_argument("--exchange", default="both",
                    choices=("all_to_all", "ring", "both"),
                    help="ici-mode exchange schedule(s) to run")
    ap.add_argument("--ring-slots", type=int, default=0,
                    help="ring accumulator cap (0 = lossless N*K)")
    ap.add_argument("--wire", default="f32",
                    choices=("f32", "bf16", "qpack8", "all"),
                    help="ici-mode supersegment wire format(s) to run "
                         "(lossy modes always run f32 too, as the PSNR "
                         "reference)")
    ap.add_argument("--schedule", default="frame",
                    choices=("frame", "waves", "both"),
                    help="frame schedule(s) to run (docs/PERF.md 'Tile "
                         "waves'): 'waves' scans the exchange+composite "
                         "per column-block wave; 'both' A/Bs them and "
                         "reports parity + the modeled overlap win")
    ap.add_argument("--wave-tiles", type=int, default=4,
                    help="column-block waves per rank block under the "
                         "waves schedule")
    ap.add_argument("--out", default=None,
                    help="also write the JSON summary to PATH (CI artifact)")
    ap.add_argument("--codec", default="zstd")
    ap.add_argument("--dir", default=None,
                    help="replay stored *_subvdi_*.npz fixtures from DIR")
    ap.add_argument("--save-fixtures", default=None,
                    help="write the generated fixtures to DIR and exit")
    args = ap.parse_args()
    n = args.ranks

    if os.environ.get(_CHILD) != "1" and os.environ.get(
            "SITPU_BENCH_REAL") != "1":
        reexec_virtual_mesh(n, _CHILD)

    import jax

    from jax import shard_map

    if os.environ.get(_CHILD) == "1":
        pin_cpu_backend()
    elif os.environ.get("SITPU_BENCH_REAL") == "1":
        # real chips: clamp the rank count to what exists instead of
        # dying in make_mesh. n=1 still
        # measures the composite kernel itself (the column exchange is an
        # identity there), which is the Pallas-vs-XLA number this bench
        # exists to capture.
        avail = jax.device_count()
        if avail < n:
            print(f"[composite_bench] {avail} real device(s) < {n} ranks; "
                  f"clamping to {avail}", file=sys.stderr, flush=True)
            n = avail
    import jax.numpy as jnp
    import numpy as np

    from scenery_insitu_tpu.config import CompositeConfig
    from scenery_insitu_tpu.core.vdi import VDI
    from scenery_insitu_tpu.io.vdi_io import (dump_path, load_vdi,
                                              pack_vdi_segments, save_vdi,
                                              unpack_vdi_segments)

    if args.dir:
        paths = sorted(glob.glob(os.path.join(args.dir, "*_subvdi_*.npz")))
        if len(paths) < n:
            raise SystemExit(f"need {n} fixtures in {args.dir}, "
                             f"found {len(paths)}")
        vdis = [load_vdi(p)[0] for p in paths[:n]]
        vdis = [VDI(jnp.asarray(v.color), jnp.asarray(v.depth))
                for v in vdis]
    else:
        vdis, metas = build_fixtures(n, args.grid, args.width, args.height,
                                     args.k, args.max_steps)
        if args.save_fixtures:
            for r, (v, m) in enumerate(zip(vdis, metas)):
                p = dump_path(args.save_fixtures, "bench", r, "subvdi")
                save_vdi(p, v, m, codec=args.codec)
            print(f"wrote {n} fixtures to {args.save_fixtures}")
            return

    k, _, h, w = vdis[0].color.shape
    comp_cfg = CompositeConfig(max_output_supersegments=args.k_out,
                               adaptive_iters=2)

    if not args.compressed:
        # --------------------------- ICI path: the production SPMD chain
        import dataclasses

        from scenery_insitu_tpu.ops.composite import modeled_exchange_traffic
        from scenery_insitu_tpu.parallel.mesh import make_mesh
        from scenery_insitu_tpu.parallel.pipeline import (
            _composite_exchanged_sched)
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = make_mesh(n)
        axis = mesh.axis_names[0]
        modes = (["all_to_all", "ring"] if args.exchange == "both"
                 else [args.exchange])
        wires = (["f32", "bf16", "qpack8"] if args.wire == "all"
                 else [args.wire])
        if "f32" not in wires:          # the lossy modes' PSNR reference
            wires = ["f32"] + wires
        scheds = (["frame", "waves"] if args.schedule == "both"
                  else [args.schedule])

        base_c = jnp.concatenate([v.color for v in vdis])
        base_d = jnp.concatenate([v.depth for v in vdis])

        per_mode = {}
        first_out = {}
        for sched in scheds:
            for mode in modes:
              for wire in wires:
                # f32 frame entries keep the bare exchange-mode key (the
                # PR-4 artifact shape); lossy wires nest under
                # "mode/wire" and the waves schedule under "waves/..."
                key = mode if wire == "f32" else f"{mode}/{wire}"
                if sched == "waves":
                    key = f"waves/{key}"
                cfg_m = dataclasses.replace(comp_cfg, exchange=mode,
                                            ring_slots=args.ring_slots,
                                            wire=wire, schedule=sched,
                                            wave_tiles=args.wave_tiles)

                def step(color, depth, cfg_m=cfg_m):  # [K,4,H,W] per rank
                    out = _composite_exchanged_sched(color, depth, n,
                                                     axis, cfg_m)
                    return out.color, out.depth

                f = jax.jit(shard_map(
                    step, mesh=mesh, in_specs=(P(axis), P(axis)),
                    out_specs=(P(None, None, None, axis),
                               P(None, None, None, axis)),
                    check_vma=False))

                stack_c = jax.device_put(base_c,
                                         NamedSharding(mesh, P(axis)))
                stack_d = jax.device_put(base_d,
                                         NamedSharding(mesh, P(axis)))

                oc, od = f(stack_c, stack_d)            # compile
                jax.block_until_ready(oc)
                first_out[key] = (np.asarray(oc), np.asarray(od))
                # measured whole-step bytes from XLA's own cost analysis —
                # the wire shrink shows up as the bytes_accessed delta
                # between wire modes of the same schedule
                from scenery_insitu_tpu.obs.device import cost_snapshot
                snap = cost_snapshot(f, stack_c, stack_d)
                total = 0.0
                # chain an input perturbation so no layer can dedupe
                # identical executions
                for it in range(args.iters):
                    t0 = time.perf_counter()
                    oc, od = f(stack_c, stack_d)
                    jax.block_until_ready(oc)
                    dt = time.perf_counter() - t0
                    total += dt
                    stack_c = stack_c.at[0, 0, 0, 0].add(
                        float(oc[0, 0, 0, 0]) * 1e-6)
                    print(f"#COMP:{key}:{it}:{dt:.6f}#")
                    print(f"#IT:{key}:{it}:{dt:.6f}#")
                per_mode[key] = {
                    "ms_per_iter": round(total / args.iters * 1000, 3),
                    # modeled per-rank exchange + composite working set —
                    # the N·K → ring_slots+K live-state lever, the
                    # per-wire ici byte shrink, and (waves) the overlap
                    # accounting (docs/PERF.md)
                    "modeled": modeled_exchange_traffic(
                        n, k, h, w, k_out=args.k_out, mode=mode,
                        ring_slots=args.ring_slots, wire=wire,
                        schedule=sched, wave_tiles=args.wave_tiles),
                    "cost_analysis": snap,
                }

        key0 = (modes[0] if scheds[0] == "frame"
                else f"waves/{modes[0]}")
        summary = {
            "metric": f"composite_ici_{n}ranks_k{k}_{w}x{h}",
            "value": per_mode[key0]["ms_per_iter"],
            "unit": "ms/iter",
            "mode": "ici",
            "exchange": per_mode,
            "ring_slots": args.ring_slots,
            "wire": args.wire,
            "schedule": args.schedule,
            "wave_tiles": args.wave_tiles,
            "backend": jax.default_backend(),
        }
        if len(scheds) == 2:
            # parity of the two SCHEDULES on the same inputs at the first
            # exchange mode: lossless waves must reproduce the frame
            # schedule's composite (the tile is a column partition of
            # the same per-pixel merge)
            fc, fd = first_out[modes[0]]
            wc, wd = first_out[f"waves/{modes[0]}"]
            dc = float(np.abs(fc - wc).max())
            fin = np.isfinite(fd) & np.isfinite(wd)
            dd = float(np.abs(fd[fin] - wd[fin]).max()) if fin.any() \
                else 0.0
            summary["schedule_parity"] = {
                "exchange": modes[0],
                "max_abs_diff_color": dc,
                "max_abs_diff_depth_finite": dd,
                "empty_slot_layout_match":
                    bool((np.isinf(fd) == np.isinf(wd)).all()),
            }
        if len(wires) > 1:
            # PSNR of each lossy wire's same-view render against the
            # SAME schedule's f32 output — the quality side of the 4×
            from scenery_insitu_tpu.core.vdi import (VDI as _VDI,
                                                     render_vdi_same_view)
            from scenery_insitu_tpu.utils.image import psnr

            _rendered = {}

            def rend(key):
                if key not in _rendered:
                    oc, od = first_out[key]
                    _rendered[key] = np.asarray(render_vdi_same_view(
                        _VDI(jnp.asarray(oc), jnp.asarray(od))))
                return _rendered[key]

            pfx = {"frame": "", "waves": "waves/"}
            summary["wire_psnr_db"] = {
                f"{pfx[s]}{mode}/{wire}":
                    round(psnr(rend(f"{pfx[s]}{mode}/{wire}"),
                               rend(f"{pfx[s]}{mode}")), 2)
                for s in scheds for mode in modes
                for wire in wires if wire != "f32"}
        if len(modes) == 2:
            # parity of the two exchange modes on the SAME (unperturbed)
            # inputs: lossless ring must match all_to_all exactly — under
            # whichever schedule actually ran (a waves-only run compares
            # its own waves/ keys instead of silently skipping)
            pfx = "" if "frame" in scheds else "waves/"
            summary["parity_schedule"] = "frame" if not pfx else "waves"
            ac, ad = first_out[pfx + "all_to_all"]
            rc, rd = first_out[pfx + "ring"]
            dc = float(np.abs(ac - rc).max())
            fin = np.isfinite(ad) & np.isfinite(rd)
            dd = float(np.abs(ad[fin] - rd[fin]).max()) if fin.any() else 0.0
            summary["parity"] = {
                "max_abs_diff_color": dc,
                "max_abs_diff_depth_finite": dd,
                "empty_slot_layout_match":
                    bool((np.isinf(ad) == np.isinf(rd)).all()),
            }
    else:
        # ------------------- compressed host hop (DCN / disk wire format)
        from scenery_insitu_tpu.ops.composite import composite_vdis

        total_comp = total_decom = 0.0
        wire_bytes = 0
        raw_bytes = n * (vdis[0].color.nbytes + vdis[0].depth.nbytes)
        comp_jit = jax.jit(lambda c, d: composite_vdis(c, d, comp_cfg))
        for it in range(args.iters):
            # pack: each rank splits + compresses its VDI per destination
            t0 = time.perf_counter()
            packed = [pack_vdi_segments(v, n, codec=args.codec)
                      for v in vdis]
            t_pack = time.perf_counter() - t0
            wire_bytes = sum(int(cl.sum() + dl.sum())
                             for _, cl, dl in packed)

            # "exchange": destination r receives segment r of every rank
            t0 = time.perf_counter()
            received = []
            for r in range(n):
                blobs = []
                for src in range(n):
                    sb, _, _ = packed[src]
                    blobs.append(sb[r])             # color seg r
                for src in range(n):
                    sb, _, _ = packed[src]
                    blobs.append(sb[n + r])         # depth seg r
                received.append(unpack_vdi_segments(blobs, k, h, w // n * n,
                                                    codec=args.codec))
            t_decom = time.perf_counter() - t0
            total_decom += t_pack + t_decom
            print(f"#DECOM:all:{it}:{t_pack + t_decom:.6f}#")

            # composite each destination's column block: received[r] holds
            # n ranks' segments concatenated on W; restack to [n,K,.,H,W/n]
            t0 = time.perf_counter()
            outs = []
            for r in range(n):
                rc = np.asarray(received[r].color).reshape(k, 4, h, n, w // n)
                rd = np.asarray(received[r].depth).reshape(k, 2, h, n, w // n)
                cc = jnp.asarray(np.moveaxis(rc, 3, 0))
                dd = jnp.asarray(np.moveaxis(rd, 3, 0))
                outs.append(comp_jit(cc, dd))
            jax.block_until_ready(outs[-1].color)
            dt = time.perf_counter() - t0
            total_comp += dt
            print(f"#COMP:all:{it}:{dt:.6f}#")
            print(f"#IT:all:{it}:{t_pack + t_decom + dt:.6f}#")
        summary = {
            "metric": f"composite_compressed_{n}ranks_k{k}_{w}x{h}",
            "value": round((total_comp + total_decom) / args.iters * 1000, 3),
            "unit": "ms/iter",
            "mode": f"compressed/{args.codec}",
            "compression_ratio": round(raw_bytes / max(wire_bytes, 1), 2),
            "decompress_ms": round(total_decom / args.iters * 1000, 3),
            "composite_ms": round(total_comp / args.iters * 1000, 3),
            "backend": jax.default_backend(),
        }
    print(json.dumps(summary))
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(summary, fh, indent=2)


if __name__ == "__main__":
    main()
