"""Benchmark harness — prints ONE JSON line for the driver.

Headline workload (BASELINE.md Config 2 scaled to the available chips): 3D
Gray-Scott reaction-diffusion advanced in-situ, rendered through the VDI
generate + composite pipeline each frame. On a single chip the composite
degenerates to N=1 but still runs the full sort-merge kernel, so the
measured ms/frame covers the whole hot path (sim → generate → composite).

Engine: the MXU slice-march raycaster (ops/slicer.py) by default — VDI
generation as banded-matmul slice resampling; the metric name carries the
true rendered grid (the slice march renders on its intermediate grid,
sized by the volume × scale, NOT SITPU_BENCH_WIDTH/HEIGHT — those apply
only to the legacy gather engine).

One process, on the backend JAX gives it: a chip belongs to one process at
a time, so nothing here probes or retries from a child. Any exception
exits non-zero.

Knobs via env (defaults are platform-dependent: on TPU the BASELINE
primary scale 512^3 x 25 frames; on CPU 128^3 x 5, which exercises the
control flow and measures nothing):
  SITPU_BENCH_GRID=512|128  SITPU_BENCH_WIDTH=1280 SITPU_BENCH_HEIGHT=720
  SITPU_BENCH_STEPS=256 SITPU_BENCH_K=16 SITPU_BENCH_FRAMES=25|5
  SITPU_BENCH_SIM_STEPS=10 SITPU_BENCH_ADAPTIVE_ITERS=2
  SITPU_BENCH_ENGINE=mxu|gather
  SITPU_BENCH_FOLD=auto|pallas_fused|pallas_seg|xla  (auto = pallas_fused
    on TPU; see config.SliceMarchConfig.fold for the schedules)
  SITPU_BENCH_AUTOTUNE=1|0  (default ON for TPU temporal runs at
    grid<=512 with no explicit FOLD: times 2 frames each of
    auto/xla at warmup and benches the winner — set 0, or
    set SITPU_BENCH_FOLD, for fixed-fold A/B captures)
  SITPU_BENCH_SCAN_FRAMES=1  (whole frame loop in ONE lax.scan launch)
  SITPU_BENCH_SIM_STEPS=0    (render-only: static field, moving camera)
  SITPU_BENCH_REBALANCE=even|occupancy  (render rebalancing: single-chip
    runs have one band either way; the knob carries the config and the
    MODELED 8-rank plan/straggler block into the artifact — the measured
    distributed A/B is benchmarks/rank_slab_bench.py --rebalance both)
  SITPU_BENCH_SCHEDULE=frame|waves  SITPU_BENCH_WAVE_TILES=4  (tile-wave
    pipelined frames — docs/PERF.md "Tile waves"; single-chip it carries
    the config + modeled 8-rank overlap into the artifact)
Roofline fields: hbm_gbps / hbm_frac_peak give achieved HBM bandwidth
(XLA cost analysis of the compiled step, or a stated lower-bound traffic
model) next to mfu_matmul, so a capture says which bound it sits at.
Baseline: the north star of 30 FPS at the 512^3 primary scale.
vs_baseline is CONFIG-MATCHED: fps/30 at grid=512 (mxu), null otherwise
(render work scales ~grid^4, sim ~grid^3 — no single exponent converts a
small-grid fps honestly); vs_baseline_unscaled = fps/30 always.
"""

import json
import os
import sys
import time


def _env_int(name, default):
    return int(os.environ.get(name, default))


# Peak tables + lookup live in obs/roofline.py now — ONE copy read by
# the MFU report fields here, the roofline verdicts and the divergence
# engine (a slice march is plausibly bandwidth-bound, in which case a
# sub-1% MFU is the wrong alarm and achieved GB/s vs peak is the
# decision metric). Re-bound under the old names for the report helpers
# below.
from scenery_insitu_tpu.obs.roofline import (  # noqa: E402
    PEAK_HBM_GBPS as _PEAK_HBM_GBPS, PEAK_TFLOPS as _PEAK_TFLOPS,
    kind_lookup as _kind_lookup)


def _peak_flops(device_kind: str, platform: str):
    v = _kind_lookup(_PEAK_TFLOPS, device_kind, platform)
    return v * 1e12 if v else None


def _peak_hbm(device_kind: str, platform: str):
    return _kind_lookup(_PEAK_HBM_GBPS, device_kind, platform)


def _frame_cost(jitted, *args):
    """Cost-analysis snapshot of the compiled frame (bytes/flops) via
    the shared ``obs.device.device_cost`` join (identical keys for
    bench artifacts, phase_bench, roofline and divergence); the caller
    falls back to a min-traffic model when the backend reports nothing.
    Lowering hits the jit/persistent compile cache — the warmup call
    already compiled this exact (shapes, donations) step."""
    from scenery_insitu_tpu.obs.device import device_cost

    snap = device_cost(jitted, *args)
    if "bytes_accessed" not in snap:
        print(f"[bench] cost analysis unavailable "
              f"({snap.get('error')})", file=sys.stderr, flush=True)
        return None, None, snap
    return snap["bytes_accessed"], snap["source"], snap


def _model_frame_bytes(grid: int, sim_steps: int, marches: int,
                       render_bytes: int, sim_fused: bool) -> float:
    """Floor-model of one frame's HBM traffic when XLA cost analysis is
    unavailable: the sim term comes from the fused-stencil schedule model
    (sim/pallas_stencil.modeled_sim_traffic — r+w of u,v per step when
    unfused), the render copy is written once and read once per march.
    Fold-state and stream traffic are schedule-dependent and EXCLUDED —
    this is a lower bound, so achieved-GB/s derived from it is also a
    lower bound."""
    from scenery_insitu_tpu.sim import pallas_stencil as ps

    vox = float(grid) ** 3
    sim = ps.modeled_sim_traffic((grid, grid, grid), sim_steps,
                                 fused=sim_fused) if sim_steps else 0.0
    render_copy = vox * render_bytes
    return sim + render_copy + marches * vox * render_bytes


def _mod_exchange(n: int, k: int, height: int, width: int,
                  exchange: str, wire: str, schedule: str = "frame",
                  wave_tiles: int = 1) -> dict:
    """Modeled per-rank sort-last exchange bytes for the configured
    wire/schedule at an n-rank shape (ops.composite.modeled_exchange_traffic
    — so the single-chip bench can still report the lever).
    ``schedule="waves"`` adds the tile-wave overlap accounting (what
    fraction of the exchange hides behind march compute)."""
    from scenery_insitu_tpu.ops.composite import modeled_exchange_traffic

    return modeled_exchange_traffic(
        n, k, height, width, k_out=k,
        mode=("ring" if exchange == "ring" else "all_to_all"), wire=wire,
        schedule=schedule, wave_tiles=wave_tiles)


def _slice_march_flops(spec, grid: int, marches: int) -> float:
    """Matmul FLOPs of one frame of the MXU engine: ``marches`` full
    marches (counting + write) × grid slices × the two banded resampling
    matmuls per slice ([Nj,Nv]@[Nv,Nu] then @[Nu,Ni]ᵀ). Elementwise work
    (sim stencil, TF, supersegment folds) excluded — matmul-only MFU."""
    nv = nu = grid  # in-plane voxel counts (cubic grid)
    per_slice = 2.0 * spec.nj * nu * (nv + spec.ni)
    return marches * grid * per_slice


def main():
    import jax
    import jax.numpy as jnp

    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.utils.backend import enable_compile_cache

    # repeat runs skip the flagship compile
    enable_compile_cache()

    from scenery_insitu_tpu.config import CompositeConfig, VDIConfig
    from scenery_insitu_tpu.core.camera import Camera, orbit
    from scenery_insitu_tpu.models.pipelines import grayscott_vdi_frame_step
    from scenery_insitu_tpu.sim import grayscott as gs

    dev = jax.devices()[0]
    platform = dev.platform
    print(f"[bench] backend={platform} device={dev.device_kind}",
          file=sys.stderr, flush=True)

    on_tpu = platform == "tpu"
    # platform-dependent defaults: TPU measures the BASELINE primary
    # scale (512^3, >=25 frames — 5-frame windows showed ~10% noise);
    # the CPU run stays small enough to finish quickly
    grid = _env_int("SITPU_BENCH_GRID", 512 if on_tpu else 128)
    width = _env_int("SITPU_BENCH_WIDTH", 1280)
    height = _env_int("SITPU_BENCH_HEIGHT", 720)
    steps = _env_int("SITPU_BENCH_STEPS", 256)
    k = _env_int("SITPU_BENCH_K", 16)
    frames = _env_int("SITPU_BENCH_FRAMES", 25 if on_tpu else 5)
    sim_steps = _env_int("SITPU_BENCH_SIM_STEPS", 10)
    ad_iters = _env_int("SITPU_BENCH_ADAPTIVE_ITERS", 2)
    # histogram: ONE counting march for all candidate thresholds (higher
    # segment fidelity than a 2-iter search AND fewer marches).
    # temporal: NO counting march in steady state — threshold carried
    # across frames (seeded by one histogram march at warmup); mxu-only,
    # so the gather engine downgrades to histogram.
    ad_mode = os.environ.get("SITPU_BENCH_ADAPTIVE_MODE", "temporal")
    fold = os.environ.get("SITPU_BENCH_FOLD", "auto")
    chunk = _env_int("SITPU_BENCH_CHUNK", 16)   # slices per fold kernel
    # 1024^3 memory plan: sim stays f32 (donated), the RENDERED field
    # copy drops to bf16 — the march's permuted volume halves to ~2.1 GB
    # and the resampling matmuls cast to bf16 regardless (see
    # models/pipelines.py render_dtype). Explicit env overrides.
    render_dtype = os.environ.get("SITPU_BENCH_RENDER_DTYPE",
                                  "bf16" if grid >= 1024 else "f32")
    # accept the long spellings; config validation only knows the short
    render_dtype = {"bfloat16": "bf16", "float32": "f32"}.get(render_dtype,
                                                              render_dtype)
    # in-plane occupancy tiles (0 = chunk skipping only; -1 = the
    # backend-resolved default, 16 on TPU — see
    # SliceMarchConfig.occupancy_vtiles)
    vtiles = _env_int("SITPU_BENCH_VTILES", -1)
    # empty-space-skipping A/B ladder (docs/PERF.md "Empty-space
    # skipping"; benchmarks/occupancy_bench.py is the dedicated A/B):
    # off | chunk | pyramid | sim — unset keeps the slicer-config
    # defaults (skip on, vtiles as above). "sim" feeds the march's
    # occupancy pyramid from ranges riding the fused sim stencil.
    skip_mode = os.environ.get("SITPU_BENCH_SKIP") or None
    if skip_mode not in (None, "off", "chunk", "pyramid", "sim"):
        raise ValueError(f"SITPU_BENCH_SKIP must be off|chunk|pyramid|sim,"
                         f" got {skip_mode!r}")
    # sim-fusion lever A/B: 0 pins the XLA roll formulation (the un-fused
    # baseline the time-fused Pallas stencil is measured against)
    sim_fused = bool(_env_int("SITPU_BENCH_SIM_FUSED", 1))
    # sort-last exchange schedule A/B (docs/PERF.md "Exchange modes"):
    # single-chip both schedules are the identity exchange, so this knob
    # exists to keep the flagship config in lockstep with the distributed
    # A/B in benchmarks/composite_bench.py (which measures the virtual
    # mesh) and to carry the choice into the artifact's config block
    exchange = os.environ.get("SITPU_BENCH_EXCHANGE", "all_to_all")
    # supersegment wire format A/B (docs/PERF.md "Wire formats"): same
    # single-chip story as the exchange knob — the distributed byte
    # shrink is composite_bench's to measure; here the knob carries the
    # config and the modeled per-wire exchange bytes into the artifact
    wire = os.environ.get("SITPU_BENCH_WIRE", "f32")
    # frame schedule A/B (docs/PERF.md "Tile waves"): single-chip frames
    # have no exchange to overlap (waves degrade to frame on the ledger),
    # so like the exchange/wire knobs this carries the config and the
    # modeled 8-rank overlap accounting into the artifact; the measured
    # distributed A/B is benchmarks/composite_bench.py --schedule both
    schedule = os.environ.get("SITPU_BENCH_SCHEDULE", "frame")
    wave_tiles = _env_int("SITPU_BENCH_WAVE_TILES", 4)
    # render-rebalancing A/B (docs/PERF.md "Render rebalancing"): a
    # single chip has one z band whatever the plan, so like the
    # exchange/wire/schedule knobs this carries the config and the
    # MODELED 8-rank plan + straggler factors into the artifact; the
    # measured distributed A/B lives in benchmarks/rank_slab_bench.py
    rebalance = os.environ.get("SITPU_BENCH_REBALANCE", "even")
    if rebalance not in ("even", "occupancy"):
        raise ValueError(f"SITPU_BENCH_REBALANCE must be even|occupancy, "
                         f"got {rebalance!r}")

    from scenery_insitu_tpu.config import SliceMarchConfig
    from scenery_insitu_tpu.ops import slicer
    engine = os.environ.get("SITPU_BENCH_ENGINE", "mxu")
    engine = slicer.resolve_engine(engine)
    if ad_mode == "temporal" and engine != "mxu":
        print("[bench] temporal mode is mxu-only; using histogram",
              file=sys.stderr, flush=True)
        obs.degrade("bench.adaptive_mode", "temporal", "histogram",
                    "temporal mode is mxu-only", warn=False)
        ad_mode = "histogram"

    base = Camera.create((0.0, 0.6, 3.0), fov_y_deg=50.0, near=0.5, far=20.0)

    def make_step(fold_name):
        from scenery_insitu_tpu.models.pipelines import \
            resolve_occupancy_cfg

        # the SAME resolver the pipeline applies, so the reported march
        # config cannot drift from the march actually benched
        mc = resolve_occupancy_cfg(
            SliceMarchConfig(fold=fold_name, chunk=chunk,
                             occupancy_vtiles=vtiles), skip_mode)
        return mc, grayscott_vdi_frame_step(
            width, height, sim_steps=sim_steps, max_steps=steps,
            vdi_cfg=VDIConfig(max_supersegments=k, adaptive_iters=ad_iters,
                              adaptive_mode=ad_mode),
            comp_cfg=CompositeConfig(max_output_supersegments=k,
                                     adaptive_iters=ad_iters,
                                     exchange=exchange, wire=wire,
                                     schedule=schedule,
                                     wave_tiles=wave_tiles,
                                     rebalance=rebalance),
            engine=engine, grid_shape=(grid, grid, grid),
            axis_sign=slicer.choose_axis(base) if engine == "mxu" else None,
            slicer_cfg=mc, render_dtype=render_dtype, sim_fused=sim_fused,
            occupancy=skip_mode)

    # the mxu step is compiled for the base camera's march regime (axis z
    # here); oscillate the orbit within ±0.35 rad so every benched frame
    # stays inside that regime no matter how many frames are requested
    temporal = ad_mode == "temporal" and engine == "mxu"

    # warmup-time fold AUTOTUNE (TPU default; SITPU_BENCH_AUTOTUNE=0 or an
    # explicit SITPU_BENCH_FOLD disables): the fold-schedule ranking has
    # disagreed with the synthetic microbench across rounds — so measure
    # 2 frames per candidate and bench the winner. Candidates:
    # the platform default and the fuses-into-the-march XLA fold (the
    # round-2 256^3 frame-context winner). Per-candidate guarded; compile
    # cache makes repeats cheap.
    # gated to <=512 grids: the tuning jits are NOT donated (each timed
    # call holds input + output sim copies), which is fine at 512^3
    # (~1 GB extra) but would OOM the 1024^3 memory plan before the
    # donated main loop even runs
    autotune = _env_int("SITPU_BENCH_AUTOTUNE",
                        1 if (on_tpu and grid <= 512) else 0)
    autotune_ms = None
    st0 = None
    if (autotune and temporal and grid <= 512
            and "SITPU_BENCH_FOLD" not in os.environ):
        st0 = gs.GrayScott.init((grid, grid, grid))
        autotune_ms = {}
        thr0 = None
        for fname in ("auto", "xla"):
            try:
                _, fs = make_step(fname)
                fr = jax.jit(lambda u_, v_, yaw, th, fs=fs:
                             fs(u_, v_, orbit(base, yaw).eye, th))
                # (not donated: st0 must survive for the main loop)
                if thr0 is None:
                    thr0 = jax.jit(fs.init_threshold)(st0.u, st0.v,
                                                      base.eye)
                c2, d2, u2, v2, t2 = fr(st0.u, st0.v, jnp.float32(0.0),
                                        thr0)
                jax.block_until_ready(c2)          # compile + settle
                t0 = time.perf_counter()
                for _ in range(2):
                    c2, d2, u2, v2, t2 = fr(u2, v2, jnp.float32(0.01), t2)
                jax.block_until_ready(c2)
                autotune_ms[fname] = round(
                    (time.perf_counter() - t0) / 2 * 1e3, 1)
            except Exception as e:
                autotune_ms[fname] = f"error: {type(e).__name__}"
                # a candidate that died is silently dropped from the
                # autotune race — ledger it so the artifact says WHY the
                # surviving fold won
                obs.degrade("bench.autotune_fold", fname, "skipped",
                            f"autotune candidate failed "
                            f"({type(e).__name__}: {str(e)[:120]})",
                            warn=False)
            finally:
                fr = fs = c2 = d2 = u2 = v2 = t2 = None
        timed = {f: m for f, m in autotune_ms.items()
                 if isinstance(m, float)}
        if timed:
            fold = min(timed, key=timed.get)
            print(f"[bench] autotune {autotune_ms} -> fold={fold}",
                  file=sys.stderr, flush=True)

    march_cfg, frame_step = make_step(fold)
    if temporal:
        def frame(u, v, yaw, thr):
            return frame_step(u, v, orbit(base, yaw).eye, thr)
    else:
        def frame(u, v, yaw):
            return frame_step(u, v, orbit(base, yaw).eye)

    # donate the carried sim/threshold state: at the 512^3 primary scale
    # u+v alone are 1 GB — without donation every frame holds two copies
    frame = jax.jit(frame, donate_argnums=(0, 1, 3) if temporal else (0, 1))
    st = st0 or gs.GrayScott.init((grid, grid, grid))
    u, v = st.u, st.v

    # warmup / compile (temporal: seed the threshold state + 2 settle
    # frames so the measured loop is the steady-state one-march regime)
    t_c = time.perf_counter()
    if temporal:
        thr = jax.jit(frame_step.init_threshold)(u, v, base.eye)
        for _ in range(3):
            c, d, u, v, thr = frame(u, v, jnp.float32(0.0), thr)
    else:
        c, d, u, v = frame(u, v, jnp.float32(0.0))
    jax.block_until_ready(c)
    compile_s = time.perf_counter() - t_c
    print(f"[bench] warmup+compile {compile_s:.1f}s", file=sys.stderr,
          flush=True)

    import math
    # SCAN_FRAMES=1: run the whole frame loop as ONE lax.scan inside ONE
    # jit call — a single executable launch for all frames. If every
    # launch is taxed (dispatch_tiny_us in hbm_bench decides), this A/B
    # isolates that tax from real device time. Per-frame means
    # of the VDI planes are returned so every frame's fold stays live
    # (no DCE of non-final frames); sim/threshold state is carried.
    scan_frames = _env_int("SITPU_BENCH_SCAN_FRAMES", 0)
    yaw_arr = jnp.asarray([0.35 * math.sin(0.7 * (i + 1))
                           for i in range(frames)], jnp.float32)
    partial_jit_donate = lambda f: jax.jit(f, donate_argnums=(0, 1, 2))
    if scan_frames and temporal:
        @partial_jit_donate
        def run_all(u, v, thr, yaws):
            def body(carry, yaw):
                u, v, thr = carry
                c, d, u, v, thr = frame_step(u, v, orbit(base, yaw).eye,
                                             thr)
                return (u, v, thr), (jnp.mean(c), jnp.mean(d))
            carry, means = jax.lax.scan(body, (u, v, thr), yaws)
            return carry, means

        # warm the scan-loop executable too (compile excluded from timing)
        (u, v, thr), _ = run_all(u, v, thr, yaw_arr)
        jax.block_until_ready(u)
        t0 = time.perf_counter()
        (u, v, thr), means = run_all(u, v, thr, yaw_arr)
        jax.block_until_ready(means)
        dt = (time.perf_counter() - t0) / frames
        c, d, u, v, thr = frame(u, v, jnp.float32(0.0), thr)
    else:
        if scan_frames:
            print("[bench] SCAN_FRAMES needs temporal mxu mode; ignoring",
                  file=sys.stderr, flush=True)
            obs.degrade("bench.scan_frames", "scan", "eager",
                        "SCAN_FRAMES needs temporal mxu mode", warn=False)
            scan_frames = 0
        t0 = time.perf_counter()
        for i in range(frames):
            yaw = yaw_arr[i]
            if temporal:
                c, d, u, v, thr = frame(u, v, yaw, thr)
            else:
                c, d, u, v = frame(u, v, yaw)
        jax.block_until_ready(c)
        dt = (time.perf_counter() - t0) / frames

    fps = 1.0 / dt
    # report what was actually rendered: the mxu engine marches the volume's
    # slices onto its intermediate grid; the gather engine marches `steps`
    # per-ray samples at (width, height)
    mfu = None
    peak = _peak_flops(dev.device_kind, platform)
    marches = 1
    if engine == "mxu":
        spec = slicer.make_spec(base, (grid, grid, grid), march_cfg)
        render_cfg = {"image": [spec.ni, spec.nj], "steps": grid,
                      "fold": spec.fold, "render_dtype": render_dtype,
                      "vtiles": spec.vtiles,
                      "skip_empty": spec.skip_empty}
        res_tag = f"{spec.ni}x{spec.nj}"
        marches = (1 if temporal else
                   2 if ad_mode == "histogram" else ad_iters + 1)
        if peak:
            mfu = round(_slice_march_flops(spec, grid, marches) * fps / peak,
                        5)
    else:
        render_cfg = {"image": [width, height], "steps": steps}
        res_tag = f"{width}x{height}"

    # roofline companion to MFU: achieved HBM GB/s over the frame, so the
    # optimization loop can tell compute-bound from bandwidth-bound
    # without xprof archaeology. XLA's cost analysis of the compiled step
    # when available; a stated lower-bound traffic model otherwise.
    frame_args = ((u, v, jnp.float32(0.0), thr) if temporal
                  else (u, v, jnp.float32(0.0)))
    hbm_bytes, hbm_src, cost_snap = _frame_cost(frame, *frame_args)
    if hbm_bytes is None and engine == "mxu":
        # the model charges a full-volume read per march — a floor only
        # for the slice march; the gather engine's traffic is sample-
        # driven and can undercut it, so no model fallback there
        rb = 2 if render_dtype in ("bf16", "bfloat16") else 4
        hbm_bytes = _model_frame_bytes(grid, sim_steps, marches, rb,
                                       sim_fused)
        hbm_src = "min_traffic_model"
    hbm_gbps = hbm_bytes / dt / 1e9 if hbm_bytes else None
    peak_bw = _peak_hbm(dev.device_kind, platform)
    # attribution plane (docs/OBSERVABILITY.md "Phase attribution"):
    # SITPU_BENCH_PROFILE=1 runs N traced frames of the SAME compiled
    # step, joins device op time back to the sitpu_* phase scopes, adds
    # roofline verdicts per phase and a divergence report against the
    # committed modeled projection — all riding inside this artifact
    profile_attr = profile_roofline = divergence = None
    if _env_int("SITPU_BENCH_PROFILE", 0):
        from scenery_insitu_tpu.obs.profiler import (ProfileCapture,
                                                     publish_attribution)
        from scenery_insitu_tpu.obs.roofline import (peaks_for,
                                                     roofline_verdicts)

        # the frame donates its inputs, so the capture threads state
        # through a closure instead of re-calling with dead buffers
        _pstate = {"u": u, "v": v, "thr": thr}
        # host-delivery meter (ISSUE 19): each profiled frame pays the
        # real delivery path — device->host copy of the frame payload,
        # CRC, and the deflate-class compress the vdi disk sink runs —
        # and the timed seconds feed ProfileCapture's host_time_fn hook
        # so attribution carries a host phase instead of folding
        # delivery into unattributed (on CPU the old normalization
        # structurally zeroed it: device op time already covered the
        # wall)
        _host_s = [0.0]

        def _deliver(c_, d_):
            import zlib as _zlib

            import numpy as _np

            t0_ = time.perf_counter()
            for leaf in (c_, d_):
                blob = _np.asarray(leaf).tobytes()
                _zlib.crc32(blob)
                _zlib.compress(blob, 6)
            _host_s[0] += time.perf_counter() - t0_

        def _profile_step():
            if temporal:
                c_, d_, _pstate["u"], _pstate["v"], _pstate["thr"] = \
                    frame(_pstate["u"], _pstate["v"], jnp.float32(0.0),
                          _pstate["thr"])
            else:
                c_, d_, _pstate["u"], _pstate["v"] = frame(
                    _pstate["u"], _pstate["v"], jnp.float32(0.0))
            _deliver(c_, d_)
            return c_

        cap = ProfileCapture(
            frames=_env_int("SITPU_BENCH_PROFILE_FRAMES", 3),
            host_time_fn=lambda: _host_s[0])
        profile_attr = cap.capture(frame, *frame_args,
                                   step=_profile_step)
        u, v, thr = _pstate["u"], _pstate["v"], _pstate["thr"]
        if profile_attr is not None:
            publish_attribution(profile_attr)
            profile_roofline = roofline_verdicts(
                profile_attr, cost_snap,
                peaks_for(dev.device_kind, platform))
            try:
                from benchmarks.divergence import (divergence_report,
                                                   latest_modeled)

                mp = latest_modeled()
                if mp:
                    with open(mp) as f:
                        mdoc = json.load(f)
                    divergence = divergence_report(
                        profile_attr, mdoc, roofline=profile_roofline,
                        measured_config={
                            "exchange": exchange, "wire": wire,
                            "schedule": schedule,
                            "sim_fused": sim_fused,
                            "render_dtype": render_dtype},
                        modeled_path=os.path.relpath(
                            mp, os.path.dirname(
                                os.path.abspath(__file__))))
            except Exception as e:   # noqa: BLE001 — a broken modeled
                # artifact must not kill the bench artifact
                obs.degrade("divergence.modeled", "modeled_projection",
                            "none", f"divergence join failed: {e}",
                            warn=False)
    # occupancy of the FINAL benched field (post-timing, host-side): the
    # artifact records how sparse the measured scene actually was — the
    # live fraction is what decides whether skip modes can pay, and the
    # per-chunk histogram says whether the sparsity is banded or diffuse
    occupancy_info = None
    if engine == "mxu":
        try:
            import numpy as _np

            from scenery_insitu_tpu.core.transfer import for_dataset
            from scenery_insitu_tpu.core.volume import Volume
            from scenery_insitu_tpu.ops import occupancy as occ_mod

            fld = (v.astype(jnp.bfloat16)
                   if render_dtype == "bf16" else v)
            pyr = occ_mod.pyramid_from_volume(
                Volume.centered(fld, extent=2.0),
                for_dataset("gray_scott"), spec)
            clf = _np.asarray(pyr.chunk_live_fractions())
            occupancy_info = {
                "mode": skip_mode or ("pyramid" if spec.vtiles > 0 else
                                      "chunk" if spec.skip_empty else
                                      "off"),
                "vtiles": spec.vtiles,
                "live_fraction": round(float(pyr.live_fraction()), 4),
                "chunk_live_hist": _np.histogram(
                    clf, bins=8, range=(0.0, 1.0))[0].tolist(),
            }
        except Exception as e:   # never let reporting kill the artifact
            occupancy_info = {"error": f"{type(e).__name__}: {e}"}
    # render-rebalance block (post-timing, host-side, engine-agnostic):
    # the z live profile of the FINAL benched field at the reference
    # 8-rank shape -> the plan slice_plan would adopt and the modeled
    # straggler factor it removes (max/mean per-rank march work; the
    # measured distributed A/B is benchmarks/rank_slab_bench.py)
    rebalance_info = None
    try:
        from scenery_insitu_tpu.core.transfer import for_dataset as _fd
        from scenery_insitu_tpu.ops import occupancy as occ_mod

        n_model = 8
        prof = occ_mod.z_live_profile(v, _fd("gray_scott"))
        even8 = occ_mod.even_plan(grid, n_model)
        plan8 = occ_mod.slice_plan(prof, grid, n_model, min_depth=4,
                                   quantum=4)
        rebalance_info = {
            "mode": rebalance,
            "modeled_ranks": n_model,
            "plan": list(plan8),
            "plan_histogram": {str(d): sum(1 for p_ in plan8 if p_ == d)
                               for d in sorted(set(plan8))},
            "straggler_even": round(
                occ_mod.straggler_factor(prof, grid, even8), 3),
            "straggler_planned": round(
                occ_mod.straggler_factor(prof, grid, plan8), 3),
        }
    except Exception as e:       # never let reporting kill the artifact
        rebalance_info = {"error": f"{type(e).__name__}: {e}"}
    # CONFIG-MATCHED vs_baseline: fps/30 only at the 512^3 primary scale
    # on the flagship engine, null otherwise — the mxu render work scales
    # ~grid^4 and the sim ~grid^3, so no single exponent converts a
    # small-grid fps to the primary metric honestly. The raw figure stays
    # available as vs_baseline_unscaled for cross-round comparison.
    matched = engine == "mxu" and grid == 512 and sim_steps > 0
    # sim_steps=0 measures the RENDER path on a static field — the same
    # semantics as the reference's own FPS harness (static volume, moving
    # camera: VolumeFromFileExample.kt:777-794), and the honest in-situ
    # split: the reference's sim runs on 20 CPU cores/node while its GPU
    # only renders (README.md:4-8), so render-only fps is the number its
    # harness would have produced
    tag = "_render_only" if sim_steps == 0 else ""
    if scan_frames:
        tag += "_scanloop"
    print(json.dumps({
        "metric": f"gray_scott_{grid}c_vdi_fps_{res_tag}_{platform}"
                  f"_1chip{tag}",
        "value": round(fps, 3),
        "unit": "frames/s",
        "vs_baseline": round(fps / 30.0, 4) if matched else None,
        "vs_baseline_unscaled": round(fps / 30.0, 4),
        "vs_baseline_note": (
            "fps/30 at the config-matched 512^3 mxu primary scale"
            if matched else
            "null: not the 512^3 mxu primary config — see "
            "vs_baseline_unscaled (raw fps/30)"),
        "ms_per_frame": round(dt * 1000.0, 2),
        "mfu_matmul": mfu,
        "hbm_gbps": round(hbm_gbps, 2) if hbm_gbps else None,
        "hbm_frac_peak": (round(hbm_gbps / peak_bw, 4)
                          if hbm_gbps and peak_bw else None),
        "hbm_bytes_per_frame": round(hbm_bytes) if hbm_bytes else None,
        "hbm_bytes_source": hbm_src,
        # observability (ISSUE 3): the per-regime device-cost snapshot of
        # the compiled frame and the fallback ledger, so the artifact
        # records WHY a number is what it is — every degradation (codec,
        # sim stencil, scan mode) that fired in this process is listed,
        # machine-readable
        "cost_analysis": {
            (f"regime={slicer.choose_axis(base)}" if engine == "mxu"
             else "gather"): cost_snap},
        # what the configured wire WOULD ship per rank at the reference
        # 8-rank distributed shape of this config (modeled — single-chip
        # runs have no exchange; composite_bench measures the real one)
        "modeled_exchange_8rank": _mod_exchange(
            8, k, height, width, exchange, wire, schedule, wave_tiles),
        "occupancy": occupancy_info,
        "rebalance": rebalance_info,
        # attribution plane (SITPU_BENCH_PROFILE=1, else nulls): traced
        # per-phase device time, roofline verdicts per phase, and the
        # model-vs-measured divergence report — docs/OBSERVABILITY.md
        "phase_attribution": profile_attr,
        "roofline_verdicts": profile_roofline,
        "divergence": divergence,
        "degradations": obs.ledger(),
        "config": {"grid": grid, **render_cfg,
                   "k": k, "frames": frames, "sim_steps": sim_steps,
                   "sim_fused": sim_fused, "exchange": exchange,
                   "wire": wire, "schedule": schedule,
                   "wave_tiles": wave_tiles, "skip": skip_mode,
                   "rebalance": rebalance,
                   "adaptive_iters": ad_iters, "adaptive_mode": ad_mode,
                   "chunk": chunk, "scan_frames": bool(scan_frames),
                   "autotune_ms": autotune_ms,
                   "compile_s": round(compile_s, 1),
                   "platform": platform, "device": dev.device_kind,
                   "assumed_peak_tflops": (peak / 1e12 if peak else None),
                   "assumed_peak_hbm_gbps": peak_bw,
                   "engine": engine},
    }), flush=True)


if __name__ == "__main__":
    main()
