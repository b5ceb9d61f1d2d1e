"""Does the in-situ session start and run right on the chip?

    python chip_smoke.py

One process, no subprocesses. Drives the main path a user drives —
``FrameworkConfig.with_overrides(...)`` → ``InSituSession(cfg, sinks=[…])
.run(n)``, the path ``examples/insitu_grayscott.py`` takes — at the
flagship width (BASELINE.json Config 2's 512³ Gray-Scott grid on its
one-chip share: 640×640 intermediate grid, K=16, temporal thresholds, 10
sim steps per frame), checks what the sink receives, and compares it with
the plain reference already in the repo: the same session with the XLA
schedules named explicitly. With four or more devices it repeats the run
on a 4-device mesh (the fused stencil on every rank's shard, z halos
from the ring neighbours) and checks that the sim state is sharded over
all four, that its field matches the one-device XLA roll, and that the
4-rank frame matches the 1-rank frame.

It fails — non-zero exit, no result line — when JAX finds no TPU, when a
phase raises, when a check fails, and when the fallback ledger holds any
row: on this path every row means a kernel or schedule gave way.

Writes ``chiprun_out/chip_smoke.json`` (device, schedules, compile
seconds, wall ms per delivered frame, peak device memory, ledger) and
``chiprun_out/chip_smoke_frame.png`` (one decoded frame). These are
bring-up observations named with their device, not benchmark results.
The last stdout line is ``{"ok": true, "device": {...}}``.

The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``<repo>/.jax_cache`` (utils/backend.enable_compile_cache).
"""

import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
GRID, K = 512, 16
WARMUP, STEADY = 2, 5
# decoded-image agreement floors (dB): default kernels vs the explicit XLA
# schedules on the same decomposition, and N ranks vs one — the floor
# tests/test_parallel.py holds the mxu engine to
PSNR_REFERENCE, PSNR_RANKS = 40.0, 27.0
# sim field, fused stencil vs XLA roll after one frame's 10 steps:
# tests/test_sim.py allows 1e-5 per fused pass of <= 4 steps
SIM_ATOL = 5e-5
# share of pixels on which the fused composite kernel may segment a ray
# differently from the XLA scan (compared at atol 1e-6)
COMPOSITE_PIXEL_SHARE = 1e-3
# the XLA schedules by name: existing values of existing knobs
REFERENCE = ("slicer.fold=xla", "composite.backend=xla",
             "sim.fused_stencil=false")


class SmokeFailure(Exception):
    """A check failed; the message is the one-line reason."""


def check(ok: bool, reason: str) -> None:
    if not ok:
        raise SmokeFailure(reason)


def overrides(grid: int, k: int, n_devices: int, extra=()) -> tuple:
    """The flagship deployment as config overrides; everything not named
    is the default."""
    return (f"sim.grid=[{grid},{grid},{grid}]", "sim.steps_per_frame=10",
            "slicer.engine=mxu", "vdi.adaptive_mode=temporal",
            f"vdi.max_supersegments={k}",
            f"composite.max_output_supersegments={k}",
            "runtime.dataset=gray_scott",
            f"mesh.num_devices={n_devices}") + tuple(extra)


class CompileMeter:
    """Counts what XLA compiled in this process (jax.monitoring): every
    backend compile request with its seconds — a persistent-cache hit
    still issues the request and spends its retrieval time there — and
    the persistent-cache hits."""

    def __init__(self):
        from jax import monitoring

        self.requests, self.seconds, self.cache_hits = 0, 0.0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            self.requests += 1
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"requests": self.requests,
                "seconds": round(self.seconds, 2),
                "cache_hits": self.cache_hits}

    def close(self) -> None:
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._duration)
        monitoring.unregister_event_listener(self._event)


def peak_bytes():
    """`peak_bytes_in_use` of the first device, where the backend reports
    it (the CPU does not)."""
    import jax

    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def decode(payload: dict):
    """One delivered VDI decoded from its own view (f32[4, H, W])."""
    import jax.numpy as jnp
    import numpy as np

    from scenery_insitu_tpu.core.vdi import VDI, render_vdi_same_view

    return np.asarray(render_vdi_same_view(
        VDI(jnp.asarray(payload["vdi_color"]),
            jnp.asarray(payload["vdi_depth"]))))


def check_payloads(got: list, k: int, nj: int, ni: int) -> dict:
    """Every delivered payload: shape, finite colors, a non-trivial share
    of covered pixels, ordered depths on live slots, contiguous frames
    from 0."""
    import numpy as np

    check([p["frame"] for p in got] == list(range(len(got))),
          f"frame indices not contiguous: {[p['frame'] for p in got]}")
    covered = []
    for p in got:
        c, d, f = p["vdi_color"], p["vdi_depth"], p["frame"]
        check(c.shape == (k, 4, nj, ni) and d.shape == (k, 2, nj, ni),
              f"frame {f}: payload shapes {c.shape} / {d.shape}, expected "
              f"{(k, 4, nj, ni)} / {(k, 2, nj, ni)}")
        check(bool(np.isfinite(c).all()), f"frame {f}: non-finite color")
        live = c[:, 3] > 0.0
        check(bool((d[:, 0][live] <= d[:, 1][live]).all())
              and bool(np.isfinite(d[:, 1][live]).all()),
              f"frame {f}: a live slot has start > end or infinite depth")
        covered.append(float(live.any(axis=0).mean()))
        check(covered[-1] > 0.01,
              f"frame {f}: only {covered[-1]:.4f} of pixels have alpha > 0")
    return {"covered_pixel_share": [round(x, 4) for x in covered]}


def run_session(ov: tuple, warmup: int, steady: int,
                meter: CompileMeter) -> dict:
    """Build the session from overrides and run warm-up then the steady
    window with a sink that keeps every delivered payload."""
    import jax
    import numpy as np

    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.ops.composite import resolve_backend
    from scenery_insitu_tpu.runtime.session import InSituSession
    from scenery_insitu_tpu.sim import pallas_stencil as ps

    cfg = FrameworkConfig().with_overrides(*ov)
    got, stamps = [], []

    def keep(index: int, payload: dict) -> None:
        got.append(payload)
        stamps.append(time.perf_counter())

    c0 = meter.snapshot()
    t0 = time.perf_counter()
    sess = InSituSession(cfg, sinks=[keep])
    sess.run(1)
    field0 = np.asarray(sess.sim.field)        # after one frame's steps
    sess.run(warmup - 1)
    warm_s = time.perf_counter() - t0
    c1 = meter.snapshot()
    steps0 = sess.obs.counters.get("compile_step", 0)
    t1 = time.perf_counter()
    sess.run(steady)
    jax.block_until_ready(sess.sim.field)
    steady_s = time.perf_counter() - t1
    c2 = meter.snapshot()
    check(sess.obs.counters.get("compile_step", 0) == steps0,
          "the session built a new step after warm-up (compile_step "
          f"{steps0} -> {sess.obs.counters.get('compile_step')})")
    check(c2["requests"] == c1["requests"],
          f"{c2['requests'] - c1['requests']} XLA compile request(s) "
          "inside the steady window")

    field = sess.sim.field
    n = sess._n_ranks
    regime = sess._slicer.choose_axis(sess.camera)
    spec = sess._slicer.make_spec(sess.camera, field.shape, cfg.slicer,
                                  axis_sign=regime, multiple_of=n)
    # what the configuration names; that nothing gave way underneath is
    # the empty ledger's statement
    cc = cfg.composite
    identity = (n == 1 and cc.backend == "auto" and cc.adaptive
                and cc.max_output_supersegments >= cfg.vdi.max_supersegments)
    deliveries = np.diff(np.asarray(stamps[warmup:]))
    return {
        "payloads": got, "field0": field0, "spec": spec,
        "sim_devices": len(field.sharding.device_set),
        "schedules": {
            "engine": sess.engine, "fold": spec.fold,
            "composite": ("identity (one rank, k_out >= k)" if identity
                          else resolve_backend(cc)),
            "stencil": ([list(p) for p in ps.schedule(
                field.sharding.shard_shape(field.shape),
                cfg.sim.steps_per_frame, ring=n > 1)[0]]
                if cfg.sim.fused_stencil else "xla_roll"),
            "matmul_dtype": spec.matmul_dtype, "vtiles": spec.vtiles,
            "chunk": spec.chunk, "regime": list(regime), "ranks": n},
        "compile": {"warmup_wall_s": round(warm_s, 2),
                    "requests": c1["requests"] - c0["requests"],
                    "seconds": round(c1["seconds"] - c0["seconds"], 2),
                    "cache_hits": c1["cache_hits"] - c0["cache_hits"]},
        "steady": {"frames": steady,
                   "wall_ms_per_frame": steady_s / steady * 1e3,
                   "delivery_interval_ms_median": (
                       float(np.median(deliveries)) * 1e3
                       if deliveries.size else None)},
        "peak_bytes_in_use": peak_bytes(),
    }


def check_dump_sink(payload: dict) -> None:
    """One delivered frame through the repo's VDI dump sink, codec named
    explicitly (zlib is stdlib; the zstd default needs `zstandard`), must
    read back bit-equal. Outside the timed window: deflating a frame
    takes seconds."""
    import numpy as np

    from scenery_insitu_tpu.io.vdi_io import dump_path, load_vdi
    from scenery_insitu_tpu.runtime.session import vdi_sink

    with tempfile.TemporaryDirectory() as codec_dir:
        vdi_sink(codec_dir, "chip_smoke", codec="zlib")(payload["frame"],
                                                        payload)
        path = dump_path(codec_dir, "chip_smoke", payload["frame"], "vdi")
        check(os.path.exists(path), f"the VDI dump sink wrote no {path}")
        vdi, _ = load_vdi(path)
    check(np.array_equal(np.asarray(vdi.color), payload["vdi_color"])
          and np.array_equal(np.asarray(vdi.depth), payload["vdi_depth"]),
          "the zlib VDI dump does not read back equal")


def check_composite_kernel(payload: dict, k: int) -> dict:
    """The fused composite kernel against the XLA scan on one real frame,
    elementwise at the tolerance tests/test_pallas.py compares them with
    (atol 1e-6). One rank with k_out >= k composites by identity on the
    default path, so the kernel gets its full-size meeting with its
    reference here. The two compilers may round a threshold comparison
    differently at a borderline pixel and segment that ray otherwise, so
    the bound is on the share of pixels that differ at all."""
    import jax.numpy as jnp
    import numpy as np

    from scenery_insitu_tpu.config import CompositeConfig
    from scenery_insitu_tpu.ops.composite import composite_vdis

    c = jnp.asarray(payload["vdi_color"])[None]
    d = jnp.asarray(payload["vdi_depth"])[None]
    out = {b: composite_vdis(c, d, CompositeConfig(
        max_output_supersegments=k, backend=b)) for b in ("pallas", "xla")}
    cp, cx = (np.asarray(out[b].color) for b in ("pallas", "xla"))
    dp, dx = (np.asarray(out[b].depth) for b in ("pallas", "xla"))
    dp, dx = (np.where(np.isfinite(x), x, -1.0) for x in (dp, dx))
    bad = ((np.abs(cp - cx) > 1e-6).any(axis=(0, 1))
           | (np.abs(dp - dx) > 1e-6).any(axis=(0, 1)))
    res = {"max_abs_color_diff": float(np.abs(cp - cx).max()),
           "pixels_differing_share": float(bad.mean())}
    check(res["pixels_differing_share"] <= COMPOSITE_PIXEL_SHARE,
          f"fused composite kernel differs from the XLA scan on "
          f"{res['pixels_differing_share']:.2e} of pixels "
          f"(> {COMPOSITE_PIXEL_SHARE}; max |dcolor| "
          f"{res['max_abs_color_diff']:.3g})")
    return res


def assert_clean_ledger(where: str) -> None:
    from scenery_insitu_tpu import obs

    rows = obs.ledger()
    check(not rows, f"{where}: the fallback ledger is not empty — " + "; ".join(
        f"{e['component']}: {e['from']} -> {e['to']} ({e['reason']})"
        for e in rows))


def smoke(grid: int = GRID, k: int = K, warmup: int = WARMUP,
          steady: int = STEADY, four: bool = False, out_dir: str = "",
          extra: tuple = ()) -> dict:
    """The whole check at one size; raises SmokeFailure with the reason.
    ``extra`` are overrides added to the one-rank run (tests name the
    CPU's schedules with them) and to the 4-device run that ``four``
    adds, whose sim is then the fused stencil on every rank's shard."""
    meter = CompileMeter()
    try:
        return _smoke(meter, grid, k, warmup, steady, four, out_dir, extra)
    finally:
        meter.close()


def _smoke(meter: CompileMeter, grid: int, k: int, warmup: int, steady: int,
           four: bool, out_dir: str, extra: tuple) -> dict:
    import numpy as np

    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.utils.image import psnr, save_png

    summary = {"grid": grid, "k": k, "warmup": warmup, "steady": steady}
    frame_k = warmup + steady - 1

    obs.clear_ledger()
    main = run_session(overrides(grid, k, 1, extra), warmup, steady, meter)
    spec = main["spec"]
    summary["payload_checks"] = check_payloads(
        main["payloads"], k, spec.nj, spec.ni)
    check_dump_sink(main["payloads"][frame_k])
    assert_clean_ledger("one-rank run")
    print(f"[chip_smoke] one rank: schedules {main['schedules']}",
          flush=True)
    print(f"[chip_smoke] one rank: compile {main['compile']}, steady "
          f"{main['steady']}, peak_bytes_in_use "
          f"{main['peak_bytes_in_use']}", flush=True)
    summary["one_rank"] = {key: main[key] for key in (
        "schedules", "compile", "steady", "peak_bytes_in_use")}
    img = decode(main["payloads"][frame_k])
    check(bool(np.isfinite(img).all()), "decoded frame is not finite")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        save_png(os.path.join(out_dir, "chip_smoke_frame.png"), img)

    # the plain reference: same seed, XLA schedules by name, untimed
    ref = run_session(overrides(grid, k, 1, REFERENCE), warmup, steady,
                      meter)
    sim_err = float(np.abs(main["field0"] - ref["field0"]).max())
    check(sim_err <= SIM_ATOL,
          f"sim field after frame 0 differs from the XLA roll reference by "
          f"{sim_err:.3g} (> {SIM_ATOL})")
    q = psnr(decode(ref["payloads"][frame_k]), img)
    check(q >= PSNR_REFERENCE,
          f"frame {frame_k} decodes {q:.1f} dB from the explicit-XLA "
          f"session (< {PSNR_REFERENCE})")
    summary["reference"] = {
        "overrides": list(REFERENCE), "schedules": ref["schedules"],
        "sim_field_max_abs_diff_frame0": sim_err,
        "decoded_psnr_db": None if np.isinf(q) else round(float(q), 2),
        "composite_kernel_vs_xla": check_composite_kernel(
            main["payloads"][frame_k], k)}
    print(f"[chip_smoke] vs explicit XLA: {summary['reference']}",
          flush=True)
    assert_clean_ledger("reference run")
    ref_field0 = ref["field0"]
    del ref

    if four:
        r4 = run_session(overrides(grid, k, 4, extra), warmup, steady,
                         meter)
        check(r4["sim_devices"] == 4,
              f"sim state spans {r4['sim_devices']} device(s), not 4")
        sim_err4 = float(np.abs(r4["field0"] - ref_field0).max())
        check(sim_err4 <= SIM_ATOL,
              f"4-rank sim field after frame 0 differs from the one-device "
              f"XLA roll reference by {sim_err4:.3g} (> {SIM_ATOL})")
        spec4 = r4["spec"]
        check_payloads(r4["payloads"], k, spec4.nj, spec4.ni)
        q4 = psnr(decode(r4["payloads"][frame_k]), img)
        check(q4 >= PSNR_RANKS,
              f"4-rank frame {frame_k} decodes {q4:.1f} dB from the "
              f"1-rank frame (< {PSNR_RANKS})")
        assert_clean_ledger("four-rank run")
        summary["four_ranks"] = {
            "schedules": r4["schedules"], "compile": r4["compile"],
            "steady": r4["steady"], "sim_devices": r4["sim_devices"],
            "sim_field_max_abs_diff_frame0": sim_err4,
            "peak_bytes_in_use": r4["peak_bytes_in_use"],
            "decoded_psnr_db_vs_one_rank": (None if np.isinf(q4)
                                            else round(float(q4), 2))}
        print(f"[chip_smoke] four ranks: {summary['four_ranks']}",
              flush=True)
    else:
        summary["four_ranks"] = "not run: fewer than 4 devices"

    summary["peak_bytes_in_use_process"] = peak_bytes()
    summary["compile_process"] = meter.snapshot()
    summary["ledger"] = obs.ledger()
    return summary


def main() -> None:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform="
                 f"{dev.platform!r} ({dev.device_kind}, "
                 f"{len(jax.devices())} device(s))")
    from importlib.metadata import PackageNotFoundError, version

    import jaxlib

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "not installed as a package"
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": libtpu}
    print(f"[chip_smoke] device {device} versions {versions}", flush=True)

    from scenery_insitu_tpu.obs.roofline import PEAK_HBM_GBPS, kind_lookup
    from scenery_insitu_tpu.utils.backend import enable_compile_cache

    kind_lookup(PEAK_HBM_GBPS, dev.device_kind, dev.platform)  # in the table?
    cache_dir = enable_compile_cache()
    out_dir = os.path.join(ROOT, "chiprun_out")
    t0 = time.perf_counter()
    try:
        summary = smoke(four=len(jax.devices()) >= 4, out_dir=out_dir)
    except SmokeFailure as e:
        sys.exit(f"chip_smoke: FAILED — {e}")
    summary.update(device=device, versions=versions,
                   compile_cache_dir=cache_dir,
                   wall_s=round(time.perf_counter() - t0, 1), claim=None)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
        json.dump(summary, f, indent=1, default=str)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
