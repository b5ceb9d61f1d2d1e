"""BASELINE.md Configs 1-5 as one runnable harness — one JSON line each.

| # | Workload (full scale)                                   | Ranks |
|---|---------------------------------------------------------|-------|
| 1 | Gray-Scott 128³, single rank                            | 1     |
| 2 | Gray-Scott 512³, VDI generate + composite               | 8     |
| 3 | Vortex-in-cell Navier-Stokes (vorticity volume) 256³    | 4     |
| 4 | Lennard-Jones MD, 1M particles, sphere render           | 8     |
| 5 | Hybrid: vortex volume + 500k tracers concurrently       | 8     |

Every config runs through InSituSession — the same frame loop, engine
selection and sinks path a production run uses — so the numbers cover
sim advance + render + fetch, not a stripped kernel.

Scale: ``--scale full`` uses the BASELINE sizes (needs real chips);
``--scale small`` (default) shrinks grids 4× and particle counts 50× so
the whole matrix runs on one host / the CI virtual mesh.

Backend: each config runs in its own subprocess. A config whose rank
count exceeds the available devices runs on a virtual CPU mesh (the
driver machine has one TPU chip; multi-rank numbers are then functional
checks, not perf). The parent process never touches a JAX backend
(this environment's TPU shim can hang backend init — see bench.py).
"""

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_CHILD = "_SITPU_CONFIGS_CHILD"

CONFIGS = {
    1: dict(kind="gray_scott", grid=128, ranks=1),
    2: dict(kind="gray_scott", grid=512, ranks=8),
    3: dict(kind="vortex", grid=256, ranks=4),
    4: dict(kind="lennard_jones", particles=1_000_000, ranks=8),
    5: dict(kind="hybrid", grid=256, particles=500_000, ranks=8),
}


def _scaled(c, scale):
    c = dict(c)
    if scale == "small":
        if "grid" in c:
            c["grid"] = max(32, c["grid"] // 4)
        if "particles" in c:
            c["particles"] = max(2000, c["particles"] // 50)
    return c


def run_config(n: int, scale: str, frames: int,
               force_ranks: int = 0) -> dict:
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.session import InSituSession
    import jax

    c = _scaled(CONFIGS[n], scale)
    if force_ranks:
        # single-chip hardware captures of the multi-rank configs: the
        # workload (grid/particles) stays full-scale, only the mesh
        # shrinks — an honest per-family device number, not Config N's
        # distributed figure. Clamp-only: forcing ranks UP would demote
        # an intended hardware run to the virtual CPU mesh silently.
        c["ranks"] = min(force_ranks, c["ranks"])
    g = c.get("grid", 0)
    volume_vdi = c["kind"] in ("gray_scott", "vortex")
    overrides = [
        f"sim.kind={c['kind']}",
        f"mesh.num_devices={c['ranks']}",
        "sim.steps_per_frame=5",
        "vdi.max_supersegments=16",
        # volume + hybrid configs: flagship engine + carried temporal
        # thresholds (mxu also runs on the CPU mesh — make_spec downgrades
        # the matmul dtype); hybrid gained temporal support in round 3, so
        # Config 5 now pays ONE march/frame like the plain VDI path
        ("vdi.adaptive_mode=temporal"
         if volume_vdi or c["kind"] == "hybrid"
         else "vdi.adaptive_mode=histogram"),
        "composite.max_output_supersegments=16",
    ]
    if volume_vdi:
        overrides.append("slicer.engine=mxu")
    if g:
        overrides.append(f"sim.grid=[{g},{g},{g}]")
    if "particles" in c:
        overrides.append(f"sim.num_particles={c['particles']}")
    cfg = FrameworkConfig().with_overrides(*overrides)

    sess = InSituSession(cfg)
    sess.run(2)                                      # warmup + compile
    t0 = time.perf_counter()
    payload = sess.run(frames)
    jax.block_until_ready(payload.get("vdi_color", payload.get("image")))
    dt = (time.perf_counter() - t0) / frames
    dev = jax.devices()[0]
    return {
        "metric": f"baseline_config_{n}",
        "workload": c,
        "mode": sess.mode,
        "engine": sess.engine,
        "ms_per_frame": round(dt * 1000.0, 2),
        "fps": round(1.0 / dt, 2),
        "frames": frames,
        "platform": dev.platform,
        "n_devices": jax.device_count(),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default="1,2,3,4,5")
    ap.add_argument("--frames", type=int, default=5)
    ap.add_argument("--scale", choices=("small", "full"), default="small")
    ap.add_argument("--timeout", type=int, default=1200,
                    help="per-config subprocess timeout (s)")
    ap.add_argument("--force-ranks", type=int, default=0,
                    help="clamp every config's mesh to N ranks (0=off): "
                    "full-scale single-chip family captures on one "
                    "chip")
    args = ap.parse_args()

    from scenery_insitu_tpu.utils.backend import virtual_mesh_env

    # this parent never touches JAX (a chip belongs to one process at a
    # time): under JAX_PLATFORMS=cpu each child gets a virtual mesh of
    # its config's rank count; otherwise it runs on the backend it is
    # given and fails there if the devices are too few
    on_cpu = os.environ.get("JAX_PLATFORMS") == "cpu"
    ok_count = 0
    for n in (int(x) for x in args.configs.split(",")):
        ranks = (min(args.force_ranks, CONFIGS[n]["ranks"])
                 if args.force_ranks else CONFIGS[n]["ranks"])
        if on_cpu:
            env = virtual_mesh_env(max(ranks, 1))
            env["_SITPU_PIN_CPU"] = "1"
        else:
            env = dict(os.environ)
        env[_CHILD] = (f"{n},{args.scale},{args.frames},"
                       f"{args.force_ranks}")
        try:
            p = subprocess.run([sys.executable, os.path.abspath(__file__)],
                               env=env, timeout=args.timeout,
                               stdout=subprocess.PIPE,
                               stderr=subprocess.STDOUT)
            out = p.stdout.decode("utf-8", "replace").strip()
            line = next((l for l in reversed(out.splitlines())
                         if l.startswith("{")), None)
            if p.returncode == 0 and line:
                print(line, flush=True)
                if '"error"' not in line:
                    ok_count += 1
            else:
                print(json.dumps({"metric": f"baseline_config_{n}",
                                  "error": f"rc={p.returncode}",
                                  "tail": out[-300:]}), flush=True)
        except subprocess.TimeoutExpired:
            from scenery_insitu_tpu import obs

            obs.degrade("bench.config_run", f"config {n}", "error_row",
                        f"child timed out after {args.timeout}s",
                        warn=False)
            print(json.dumps({"metric": f"baseline_config_{n}",
                              "error": f"timeout {args.timeout}s"}),
                  flush=True)
    if ok_count == 0:
        # all configs failed: a caller treating exit 0 as a done-marker
        # must not archive an all-error artifact
        sys.exit(1)


if __name__ == "__main__":
    if _CHILD in os.environ:
        if os.environ.get("_SITPU_PIN_CPU") == "1":
            from scenery_insitu_tpu.utils.backend import pin_cpu_backend
            pin_cpu_backend()
        parts = os.environ[_CHILD].split(",")
        n, scale, frames = parts[0], parts[1], parts[2]
        force = int(parts[3]) if len(parts) > 3 else 0
        print(json.dumps(run_config(int(n), scale, int(frames),
                                    force_ranks=force)),
              flush=True)
    else:
        main()
