"""1→N chip scaling sweep — the BASELINE "1→8 chip scaling efficiency"
metric, ready to run the moment multi-chip hardware appears (VERDICT r2
item 8). One command, one JSON line out:

    python benchmarks/scaling_bench.py                  # virtual CPU mesh
    SITPU_BENCH_REAL=1 python benchmarks/scaling_bench.py   # real chips

For each mesh size n (powers of two up to --max-ranks, clipped to the
device count) the sweep runs the PRODUCTION steady-state path — the
distributed temporal MXU VDI step (one march/frame, carried thresholds) —
on the same global workload (strong scaling; --mode weak scales the
z extent with n) and reports per-n FPS, speedup vs n=1, parallel
efficiency, and the all_to_all share measured by separately timing the
column-exchange stage on that n's own VDI tensors (the split forces a
materialization, so the share is an upper bound — same caveat as
benchmarks/phase_bench.py; for the ground-truth overlap use
``session.run(profile_dir=...)`` and xprof).

Inputs are chained across frames (the sim state advances through the
measured step) so no execution-dedup layer can fake the timing.

Two scale-OUT modes ride along (ISSUE 14; docs/MULTIHOST.md):

- ``--mode hosts``: WEAK-scaling growing-HOST runs through the real
  multi-process subprocess harness (testing/multiproc.py) — fixed
  per-rank volume, 1..--max-hosts jax.distributed processes, each
  running the host-path two-level composite (per-host domain partials
  on the local mesh, qpack8-capable tile streams over loopback DCN,
  incremental head assembly). Reports per-host-count ms/frame, weak
  efficiency, and MEASURED per-host DCN bytes next to the
  ``modeled_dcn_traffic`` prediction.
- ``--mode hier-device``: the device-path hierarchical composite
  (domains as mesh sub-axes) vs the flat composite on THIS machine's
  devices (on the virtual CPU mesh it doubles as the emulated-path
  timing).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_CHILD = "_SITPU_SCALING_CHILD"

# ------------------------------------------------- hosts mode (harness)

HOSTS_G = 24          # in-plane grid of the weak-scaling scene
HOSTS_GPR = 6         # z slices per RANK (fixed — weak scaling)
HOSTS_K = 6
HOSTS_KOUT = 8
HOSTS_W = HOSTS_H = 16


def _entry_weak(ctx):
    """Harness worker of --mode hosts: render `frames` frames of the
    host-path two-level composite at a FIXED per-rank volume; the head
    (process 0) times barrier->assembled-frame and writes the row."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.config import CompositeConfig, VDIConfig
    from scenery_insitu_tpu.core.camera import Camera
    from scenery_insitu_tpu.core.transfer import for_dataset
    from scenery_insitu_tpu.core.vdi import VDIMetadata
    from scenery_insitu_tpu.parallel import multihost
    from scenery_insitu_tpu.parallel.hier import (assemble_hier_frame,
                                                  domain_partial_vdi_step,
                                                  modeled_dcn_traffic,
                                                  publish_partial_tiles)
    from scenery_insitu_tpu.parallel.mesh import make_mesh
    from scenery_insitu_tpu.parallel.pipeline import shard_volume
    from scenery_insitu_tpu.runtime.streaming import (VDIPublisher,
                                                      VDISubscriber)
    from scenery_insitu_tpu.sim import grayscott as gs

    frames, dcn_wire = int(ctx.args[0]), ctx.args[1]
    pid, nproc = ctx.process_id, ctx.num_processes
    rec = obs.Recorder(enabled=True, rank=pid)
    obs.set_recorder(rec)

    d_local = len(jax.local_devices())
    n_total = nproc * d_local
    gz = HOSTS_GPR * n_total
    g = HOSTS_G
    st = gs.GrayScott.init((gz, g, g), n_seeds=4)      # same seed everywhere
    field = np.asarray(st.v)
    dn = HOSTS_GPR
    rank0 = pid * d_local
    lo, hi = rank0 * dn, (rank0 + d_local) * dn
    halo_lo = field[lo - 1:lo] if lo > 0 else field[0:1]
    halo_hi = field[hi:hi + 1] if hi < gz else field[gz - 1:gz]

    tf = for_dataset("gray_scott")
    cam = Camera.create((0.0, 0.4, 3.0), fov_y_deg=50.0, near=0.5,
                        far=20.0)
    origin = jnp.array([-1.0, -1.0, -1.0], jnp.float32)
    spacing = jnp.array([2.0 / g, 2.0 / g, 2.0 / gz], jnp.float32)
    vcfg = VDIConfig(max_supersegments=HOSTS_K, adaptive_iters=2)
    ccfg = CompositeConfig(max_output_supersegments=HOSTS_KOUT,
                           adaptive_iters=2)

    mesh = make_mesh(d_local, devices=jax.local_devices())
    step = domain_partial_vdi_step(mesh, tf, HOSTS_W, HOSTS_H, vcfg, ccfg,
                                   max_steps=24, rank_offset=rank0,
                                   n_total=n_total)
    local = shard_volume(jnp.asarray(field[lo:hi]), mesh)
    hlo, hhi = jnp.asarray(halo_lo), jnp.asarray(halo_hi)
    meta = VDIMetadata.create(np.eye(4, dtype=np.float32),
                              np.eye(4, dtype=np.float32),
                              volume_dims=(gz, g, g),
                              window_dims=(HOSTS_W, HOSTS_H))

    precision = "qpack8" if dcn_wire == "qpack8" else "f32"
    pub = VDIPublisher(bind="tcp://127.0.0.1:0", codec="zlib",
                       precision=precision, epoch=300 + pid)
    multihost.kv_put_bytes(f"ws/ep/{pid}", pub.endpoint.encode())
    multihost.barrier("ws_eps")
    subs = None
    if pid == 0:
        subs = {h: VDISubscriber(connect=multihost.kv_get_bytes(
            f"ws/ep/{h}").decode()) for h in range(nproc)}
        time.sleep(0.5)
    multihost.barrier("ws_subs")

    sent_total = 0
    recv_base = 0
    frame_ms = []
    for f in range(frames + 1):          # frame 0 = compile, dropped
        multihost.barrier(f"ws_f{f}", timeout_ms=300_000)
        t0 = time.perf_counter()
        acc_c, acc_d = step(local, origin, spacing, cam, hlo, hhi)
        m = meta._replace(index=np.int32(f))
        sent = publish_partial_tiles(pub, acc_c, acc_d, m, tiles=d_local)
        if pid == 0:
            frame, degraded = assemble_hier_frame(
                subs, nproc, ccfg, tiles=d_local, timeout_ms=120_000)
            assert frame is not None and not degraded, (f, degraded)
            dt = (time.perf_counter() - t0) * 1000.0
            if f > 0:
                frame_ms.append(dt)
            else:
                # the dropped compile frame's receives must not inflate
                # the per-frame received-bytes average below
                recv_base = int(rec.counters.get("dcn_bytes_received", 0))
        if f > 0:
            sent_total += sent
    multihost.barrier("ws_done", timeout_ms=300_000)
    pub.close()

    if pid == 0:
        for s in subs.values():
            s.close()
        row = {
            "hosts": nproc, "devices_per_host": d_local,
            "n_ranks": n_total, "grid": [gz, g, g],
            "frames": frames, "dcn_wire": dcn_wire,
            "ms_per_frame": round(float(np.mean(frame_ms)), 2),
            "fps": round(1000.0 / float(np.mean(frame_ms)), 3),
            "dcn_bytes_sent_per_host_measured": sent_total // frames,
            "dcn_bytes_received_head_measured":
                (int(rec.counters.get("dcn_bytes_received", 0))
                 - recv_base) // frames,
            "modeled": modeled_dcn_traffic(
                nproc, d_local, HOSTS_K, HOSTS_H, HOSTS_W,
                dcn_wire=dcn_wire),
        }
        with open(os.path.join(ctx.workdir,
                               f"ws_hosts_{nproc}.json"), "w") as fp:
            json.dump(row, fp)


def _hosts_mode(args) -> None:
    """Parent of --mode hosts: one harness fleet per host count."""
    import tempfile

    from scenery_insitu_tpu.testing import multiproc

    sweep = []
    sizes = [h for h in (1, 2, 4, 8) if h <= args.max_hosts]
    with tempfile.TemporaryDirectory() as workdir:
        for hosts in sizes:
            t0 = time.perf_counter()
            results = multiproc.run_multiproc(
                "benchmarks.scaling_bench:_entry_weak", n_procs=hosts,
                devices_per_proc=args.devices_per_host, workdir=workdir,
                args=(args.frames, args.dcn_wire), timeout_s=600.0)
            bad = [r for r in results if not r.ok]
            if bad:
                print(f"[hier] hosts={hosts} FAILED:\n{bad[0].output}",
                      file=sys.stderr, flush=True)
                sweep.append({"hosts": hosts, "error":
                              f"worker {bad[0].process_id} rc="
                              f"{bad[0].returncode}"})
                continue
            row = json.load(open(os.path.join(workdir,
                                              f"ws_hosts_{hosts}.json")))
            row["wall_s"] = round(time.perf_counter() - t0, 1)
            sweep.append(row)
            print(f"[hier] hosts={hosts}: {row['ms_per_frame']} ms/frame"
                  f" dcn {row['dcn_bytes_sent_per_host_measured']} "
                  f"B/host/frame", file=sys.stderr, flush=True)
    base = next((r.get("fps") for r in sweep if r.get("hosts") == 1
                 and "fps" in r), None)
    for row in sweep:
        if base and "fps" in row:
            # weak scaling: ideal keeps per-host throughput flat
            row["weak_efficiency"] = round(row["fps"] / base, 3)
    print(json.dumps({
        "metric": "hier_weak_scaling_cpu",
        "value": (sweep[-1].get("weak_efficiency")
                  if sweep and "weak_efficiency" in sweep[-1] else None),
        "unit": "weak_parallel_efficiency",
        "sweep": sweep,
        "config": {"mode": "hosts", "per_rank_z": HOSTS_GPR,
                   "grid_inplane": HOSTS_G, "k": HOSTS_K,
                   "frames": args.frames, "dcn_wire": args.dcn_wire,
                   "devices_per_host": args.devices_per_host,
                   "note": ("host-path two-level composite through the "
                            "subprocess harness: per-host local-mesh "
                            "domain partials + tile streams over "
                            "loopback DCN + incremental head assembly; "
                            "ms/frame includes the head merge")},
    }, indent=2), flush=True)


def _hier_device_mode(args) -> None:
    """--mode hier-device: flat vs hierarchical (domains as mesh
    sub-axes) A/B of the production temporal MXU step on this machine's
    devices."""
    from scenery_insitu_tpu.utils.backend import (enable_compile_cache,
                                                  pin_cpu_backend,
                                                  reexec_virtual_mesh)

    real = os.environ.get("SITPU_BENCH_REAL") == "1"
    if os.environ.get(_CHILD) != "1" and not real:
        reexec_virtual_mesh(8, _CHILD)
    if os.environ.get(_CHILD) == "1":
        pin_cpu_backend()
    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from scenery_insitu_tpu.config import (CompositeConfig,
                                           SliceMarchConfig,
                                           TopologyConfig, VDIConfig)
    from scenery_insitu_tpu.core.camera import Camera
    from scenery_insitu_tpu.core.transfer import for_dataset
    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.parallel.hier import modeled_dcn_traffic
    from scenery_insitu_tpu.parallel.mesh import make_mesh
    from scenery_insitu_tpu.parallel.pipeline import (
        distributed_initial_threshold_mxu,
        distributed_vdi_step_mxu_temporal, shard_volume)
    from scenery_insitu_tpu.parallel.topology import make_topology_mesh
    from scenery_insitu_tpu.sim import grayscott as gs

    ndev = jax.device_count()
    platform = jax.devices()[0].platform
    g = args.grid
    tf = for_dataset("gray_scott")
    cam = Camera.create((0.0, 0.5, 3.0), fov_y_deg=50.0, near=0.5,
                        far=20.0)
    vcfg = VDIConfig(max_supersegments=args.k, adaptive_mode="temporal")
    ccfg = CompositeConfig(max_output_supersegments=args.k,
                           adaptive_iters=2)
    mcfg = SliceMarchConfig(
        matmul_dtype="f32" if platform != "tpu" else "bf16")
    origin = jnp.array([-1.0, -1.0, -1.0], jnp.float32)
    spacing = jnp.array([2.0 / g] * 3, jnp.float32)
    st = gs.GrayScott.init((g, g, g), n_seeds=4)

    def run(mesh, topology):
        spec = slicer.make_spec(cam, (g, g, g), mcfg, multiple_of=ndev)
        step = distributed_vdi_step_mxu_temporal(
            mesh, tf, spec, vcfg, ccfg, topology=topology)
        seed = distributed_initial_threshold_mxu(mesh, tf, spec, vcfg)
        v = shard_volume(st.v, mesh)
        thr = seed(v, origin, spacing, cam)
        (vdi, _), thr = step(v, origin, spacing, cam, thr)
        jax.block_until_ready(vdi.color)
        t0 = time.perf_counter()
        for _ in range(args.frames):
            (vdi, _), thr = step(v, origin, spacing, cam, thr)
        jax.block_until_ready(vdi.color)
        dt = (time.perf_counter() - t0) / args.frames * 1000.0
        return dt, np.asarray(vdi.color)

    flat_ms, flat_c = run(make_mesh(ndev), None)
    out = {"metric": f"hier_device_ab_{platform}", "devices": ndev,
           "grid": g, "k": args.k, "flat_ms_per_frame": round(flat_ms, 2),
           "hier": {}}
    hosts_sizes = [h for h in (2, 4) if ndev % h == 0 and ndev // h >= 1
                   and h <= ndev]
    for hosts in hosts_sizes:
        tcfg = TopologyConfig(num_hosts=hosts, dcn_wire=args.dcn_wire)
        mesh, topo = make_topology_mesh(tcfg)
        ms, c = run(mesh, tcfg)
        spec_ni = slicer.make_spec(cam, (g, g, g), mcfg,
                                   multiple_of=ndev).ni
        out["hier"][f"{hosts}x{ndev // hosts}"] = {
            "ms_per_frame": round(ms, 2),
            "vs_flat": round(ms / flat_ms, 3) if flat_ms else None,
            "parity_max_abs_diff": float(np.abs(c - flat_c).max()),
            "modeled_dcn": modeled_dcn_traffic(
                hosts, ndev // hosts, args.k, spec_ni, spec_ni,
                dcn_wire=args.dcn_wire),
        }
        print(f"[hier-device] {hosts}x{ndev // hosts}: {ms:.1f} ms "
              f"(flat {flat_ms:.1f})", file=sys.stderr, flush=True)
    if not hosts_sizes:
        out["note"] = (f"{ndev} device(s) cannot split into >1 domain — "
                       "degenerate capture (flat only)")
    # one line: callers read the LAST stdout line
    print(json.dumps(out), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-ranks", type=int, default=8)
    ap.add_argument("--grid", type=int, default=64,
                    help="global cubic grid (strong) / per-chip z base (weak)")
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--frames", type=int, default=10)
    ap.add_argument("--sim-steps", type=int, default=5)
    ap.add_argument("--mode",
                    choices=("strong", "weak", "hosts", "hier-device"),
                    default="strong")
    ap.add_argument("--max-hosts", type=int, default=2,
                    help="hosts mode: largest process count in the sweep")
    ap.add_argument("--devices-per-host", type=int, default=2,
                    help="hosts mode: virtual devices per process")
    ap.add_argument("--dcn-wire", default="f32",
                    choices=("f32", "bf16", "qpack8"),
                    help="wire format of the inter-host (DCN) hop")
    args = ap.parse_args()

    if args.mode == "hosts":
        return _hosts_mode(args)
    if args.mode == "hier-device":
        return _hier_device_mode(args)

    from scenery_insitu_tpu.utils.backend import (enable_compile_cache,
                                                  pin_cpu_backend,
                                                  reexec_virtual_mesh)

    real = os.environ.get("SITPU_BENCH_REAL") == "1"
    if os.environ.get(_CHILD) != "1" and not real:
        reexec_virtual_mesh(args.max_ranks, _CHILD)

    import jax

    from jax import shard_map

    if os.environ.get(_CHILD) == "1":
        pin_cpu_backend()
    enable_compile_cache()

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from scenery_insitu_tpu.config import (CompositeConfig, SliceMarchConfig,
                                           VDIConfig)
    from scenery_insitu_tpu.core.camera import Camera
    from scenery_insitu_tpu.core.transfer import for_dataset
    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.parallel.mesh import make_mesh
    from scenery_insitu_tpu.parallel.pipeline import (
        _exchange_columns, distributed_initial_threshold_mxu,
        distributed_vdi_step_mxu_temporal, shard_volume)
    from scenery_insitu_tpu.sim import grayscott as gs

    ndev = jax.device_count()
    sizes = [n for n in (1, 2, 4, 8, 16, 32)
             if n <= min(args.max_ranks, ndev)]
    platform = jax.devices()[0].platform
    tf = for_dataset("gray_scott")
    cam = Camera.create((0.0, 0.5, 3.0), fov_y_deg=50.0, near=0.5, far=20.0)
    vdi_cfg = VDIConfig(max_supersegments=args.k, adaptive_mode="temporal")
    comp_cfg = CompositeConfig(max_output_supersegments=args.k,
                               adaptive_iters=2)
    mcfg = SliceMarchConfig(
        matmul_dtype="f32" if platform != "tpu" else "bf16")
    axis = "ranks"
    sweep = []

    for n in sizes:
        g = args.grid
        gz = g if args.mode == "strong" else g * n
        if gz % n:
            print(f"[scaling] skip n={n}: z={gz} not divisible",
                  file=sys.stderr, flush=True)
            continue
        mesh = make_mesh(n, axis)
        # one spec per n is fine (ni rounded per n); strong scaling keeps
        # the IMAGE workload identical because the volume extent is fixed
        spec = slicer.make_spec(cam, (gz, g, g), mcfg,
                                multiple_of=max(sizes))
        origin = jnp.array([-1.0, -1.0, -1.0], jnp.float32)
        spacing = jnp.array([2.0 / g, 2.0 / g, 2.0 / gz], jnp.float32)

        step = distributed_vdi_step_mxu_temporal(mesh, tf, spec, vdi_cfg,
                                                 comp_cfg)
        seed = distributed_initial_threshold_mxu(mesh, tf, spec, vdi_cfg)
        sim = jax.jit(lambda u, v: gs.multi_step(
            gs.GrayScott(u, v, gs.GrayScottParams.create()),
            args.sim_steps))

        st = gs.GrayScott.init((gz, g, g), n_seeds=4)
        u = shard_volume(st.u, mesh)
        v = shard_volume(st.v, mesh)

        t_c = time.perf_counter()
        stw = sim(u, v)
        thr = seed(stw.v, origin, spacing, cam)
        (vdi, _), thr = step(stw.v, origin, spacing, cam, thr)
        jax.block_until_ready(vdi.color)
        compile_s = time.perf_counter() - t_c

        t0 = time.perf_counter()
        for _ in range(args.frames):
            stw = sim(stw.u, stw.v)
            (vdi, _), thr = step(stw.v, origin, spacing, cam, thr)
        jax.block_until_ready(vdi.color)
        dt = (time.perf_counter() - t0) / args.frames

        # all_to_all share: time ONLY the column exchange at this n's
        # true wire shape — each rank holds a FULL-width sub-VDI
        # [K, 4, Nj, Ni] pre-exchange (split-stage upper bound)
        a2a_ms = 0.0
        if n > 1:
            def exch_roundtrip(c, d):
                # exchange, then locally repack the received column blocks
                # to the input layout so outputs CHAIN into the next
                # iteration's inputs (dedup-proof); the local repack is a
                # per-rank transpose, small next to the ICI transfer, and
                # keeps a2a_ms an upper bound like the split itself
                def rt(x):
                    parts = _exchange_columns(x, n, axis)  # [n, ..., W/n]
                    return jnp.moveaxis(parts, 0, -2).reshape(x.shape)

                return rt(c), rt(d)

            exch = jax.jit(shard_map(
                exch_roundtrip, mesh=mesh, in_specs=(P(axis), P(axis)),
                out_specs=(P(axis), P(axis)), check_vma=False))
            sh = NamedSharding(mesh, P(axis))
            cs = jax.device_put(jnp.tile(vdi.color, (n, 1, 1, 1)), sh)
            ds = jax.device_put(jnp.tile(vdi.depth, (n, 1, 1, 1)), sh)
            jax.block_until_ready(exch(cs, ds))        # warm
            t0 = time.perf_counter()
            for _ in range(args.frames):
                cs, ds = exch(cs, ds)                  # chained inputs
            jax.block_until_ready(ds)
            a2a_ms = (time.perf_counter() - t0) / args.frames * 1000.0

        sweep.append({"n": n, "grid": [gz, g, g],
                      "fps": round(1.0 / dt, 3),
                      "ms_per_frame": round(dt * 1000.0, 2),
                      "all_to_all_ms": round(a2a_ms, 2),
                      "all_to_all_share": round(a2a_ms / (dt * 1000.0), 4),
                      "compile_s": round(compile_s, 1)})
        print(f"[scaling] n={n}: {sweep[-1]['fps']} fps "
              f"(a2a {a2a_ms:.1f} ms)", file=sys.stderr, flush=True)

    base = sweep[0]["fps"] if sweep else 0.0
    for row in sweep:
        row["speedup"] = round(row["fps"] / base, 3) if base else None
        if args.mode == "strong":
            row["efficiency"] = (round(row["fps"] / (base * row["n"]), 3)
                                 if base else None)
        else:
            row["efficiency"] = (round(row["fps"] / base, 3)
                                 if base else None)

    print(json.dumps({
        "metric": f"scaling_{args.mode}_{platform}",
        "value": sweep[-1]["efficiency"] if sweep else None,
        "unit": "parallel_efficiency",
        "sweep": sweep,
        "config": {"mode": args.mode, "grid": args.grid, "k": args.k,
                   "frames": args.frames, "platform": platform,
                   "devices": ndev,
                   "note": ("all_to_all numbers are split-stage upper "
                            "bounds; xprof a profile_dir run for the "
                            "fused overlap")},
    }), flush=True)


if __name__ == "__main__":
    main()
