"""Micro-roofline: what does THIS chip actually deliver?

The round-5 window-1 flagship capture reported 29 GB of HBM traffic per
419 ms frame — 69 GB/s achieved against an assumed 819 GB/s v5e peak,
with MFU at 0.5%. Two very different diagnoses fit that datapoint:

  (a) our kernels are occupancy/latency-bound and leave ~10x bandwidth
      on the table (fixable by schedule work), or
  (b) the chip as reached then simply delivered far less than the
      data-sheet peak, and the frame was already near ITS roofline
      (schedule A/Bs will all come back flat — which is exactly what
      rounds 3-5 measured: pallas 420 ms, xla 482 ms, pallas_seg
      419 ms).

This 30-second harness settles it with four primitives, each timed on
device via async dispatch + one final block:

  copy     y = x                 (pure HBM stream, 2 bytes/elem-byte)
  axpy     y = 2x + y            (stream + 1 flop)
  stencil  7-point Gray-Scott-shaped Laplacian on 512^3 (the sim's
           memory pattern: ~3 arrays of traffic per step when fused)
  sim      10 real Gray-Scott steps at 512^3 (the flagship's in-situ
           component, exactly as bench.py runs it)
  matmul   8k x 8k x 8k bf16 (the MXU sanity point)

Prints one JSON line: achieved GB/s per primitive + TFLOP/s for the
matmul + the implied best-case frame time for the flagship's measured
29 GB, so the next capture can say "the frame is at N% of the COPY
roofline" instead of quoting a data-sheet number the chip never hits.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def _time(fn, *args, iters=10, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main():
    if os.environ.get("SITPU_CPU") == "1":
        from scenery_insitu_tpu.utils.backend import pin_cpu_backend
        pin_cpu_backend()
    from scenery_insitu_tpu.utils.backend import enable_compile_cache
    enable_compile_cache()
    dev = jax.devices()[0]
    n = int(os.environ.get("SITPU_HBM_BENCH_MB", "512")) * (1 << 20) // 4
    x = jnp.arange(n, dtype=jnp.float32)  # 512 MB by default
    nbytes = x.size * 4

    gb = 1e9

    # Incremental artifact (earlier runs that died part-way left
    # DANGLING `.partial` stdout dumps that no tooling could parse).
    # With SITPU_HBM_BENCH_OUT set, every landed
    # primitive ATOMICALLY rewrites a well-formed JSON artifact with
    # {"partial": true, "points": {...so far...}} — a timeout at any
    # instant leaves a loadable file whose completed points still carry
    # their numbers; the final summary rewrites it with partial: false.
    out_path = os.environ.get("SITPU_HBM_BENCH_OUT", "")
    points = {}

    def _write_artifact(record):
        if not out_path:
            return
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(record, f)
        os.replace(tmp, out_path)

    def partial(**kv):
        # one line per landed primitive: if the run is cut short, the
        # primitives that DID run still carry their numbers
        print(json.dumps({"partial": kv}), flush=True)
        points.update(kv)
        _write_artifact({"metric": "hbm_micro_roofline",
                         "device": dev.device_kind,
                         "platform": dev.platform,
                         "partial": True, "points": dict(points)})

    # dispatch tax first (trivial compiles, and it qualifies every
    # number that follows): a tiny jitted op called back-to-back with
    # async dispatch exactly like the bench frame loop, then a dependent
    # chain (pipelined transports hide round trips; a synchronous shim
    # cannot)
    tiny = jax.jit(lambda s: s + 1.0)
    t_tiny = _time(tiny, jnp.float32(0.0), iters=100, warmup=3)
    partial(dispatch_tiny_us=round(t_tiny * 1e6, 1))

    def chain(s, n=10):
        for _ in range(n):
            s = tiny(s)
        return s
    t_chain = _time(chain, jnp.float32(0.0), iters=5) / 10.0
    partial(dispatch_chain_us=round(t_chain * 1e6, 1))

    copy = jax.jit(lambda a: a + 0.0)
    axpy = jax.jit(lambda a, b: 2.0 * a + b)
    t_copy = _time(copy, x)                      # read + write
    partial(copy_gbps=round(2 * nbytes / t_copy / gb, 1))
    t_axpy = _time(axpy, x, x)                   # 2 reads + write
    partial(axpy_gbps=round(3 * nbytes / t_axpy / gb, 1))

    m = 8192
    a = jnp.zeros((m, m), jnp.bfloat16) + 0.5
    mm = jax.jit(lambda p, q: (p @ q).astype(jnp.bfloat16))
    t_mm = _time(mm, a, a, iters=5)
    partial(matmul_tflops=round(2.0 * m ** 3 / t_mm / 1e12, 1))

    # the sim's shape of traffic: 7-point Laplacian over 512^3
    g = int(os.environ.get("SITPU_HBM_BENCH_GRID", "512"))
    u = jnp.zeros((g, g, g), jnp.float32) + 0.25

    @jax.jit
    def stencil(a):
        return (jnp.roll(a, 1, 0) + jnp.roll(a, -1, 0)
                + jnp.roll(a, 1, 1) + jnp.roll(a, -1, 1)
                + jnp.roll(a, 1, 2) + jnp.roll(a, -1, 2) - 6.0 * a)

    t_sten = _time(stencil, u, iters=5)          # >= read + write
    partial(stencil_gbps=round(2 * 4 * g ** 3 / t_sten / gb, 1))

    # LAST: the real sim's 10 steps — multi_step_fast walks Mosaic
    # compile probes for the fused stencil schedules, much the costliest
    # compiles here; everything decisive has already been printed if the
    # window closes on it
    from scenery_insitu_tpu.sim import grayscott as gs
    st = gs.GrayScott.init((g, g, g))
    sim10 = jax.jit(lambda s: gs.multi_step_fast(s, 10))
    t_sim = _time(sim10, st, iters=3)
    partial(sim10_ms=round(t_sim * 1e3, 2))

    sim_bytes = 10 * 4 * g ** 3 * 4.0            # 10 steps x (r+w of u,v)
    out = {
        "metric": "hbm_micro_roofline",
        "device": dev.device_kind, "platform": dev.platform,
        "partial": False,
        "copy_gbps": round(2 * nbytes / t_copy / gb, 1),
        "axpy_gbps": round(3 * nbytes / t_axpy / gb, 1),
        "stencil_gbps": round(2 * 4 * g ** 3 / t_sten / gb, 1),
        "sim10_ms": round(t_sim * 1e3, 2),
        "sim10_gbps_floor": round(sim_bytes / t_sim / gb, 1),
        "matmul_tflops": round(2.0 * m ** 3 / t_mm / 1e12, 1),
        "dispatch_tiny_us": round(t_tiny * 1e6, 1),
        "dispatch_chain_us": round(t_chain * 1e6, 1),
        "buf_mb": nbytes >> 20,
        "flagship_frame_gb": 29.0,
        "implied_frame_ms_at_copy_bw": round(
            29.0 * gb / (2 * nbytes / t_copy) * 1e3, 1),
    }
    print(json.dumps(out), flush=True)
    # the completed artifact keeps the incremental schema's "points"
    # nesting alongside the flat summary keys, so a reader written
    # against either layout works on both partial and final files
    _write_artifact({**out, "points": dict(points)})


if __name__ == "__main__":
    main()
