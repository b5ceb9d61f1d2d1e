"""Phase profiler for the MXU slice-march frame (diagnostic; VERDICT weak
#6): times each stage of the flagship pipeline separately so optimization
targets facts, not guesses. Usage: python benchmarks/profile_march.py
[grid]."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def timeit(fn, *args, n=3, label=""):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / n * 1000
    print(f"{label:42s} {dt:9.1f} ms", flush=True)
    return dt


def main():
    if os.environ.get("SITPU_CPU") == "1":
        from scenery_insitu_tpu.utils.backend import pin_cpu_backend
        pin_cpu_backend()
    from scenery_insitu_tpu.config import (CompositeConfig, SliceMarchConfig,
                                           VDIConfig)
    from scenery_insitu_tpu.core.camera import Camera
    from scenery_insitu_tpu.core.transfer import for_dataset
    from scenery_insitu_tpu.core.volume import Volume
    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.ops import supersegments as ss
    from scenery_insitu_tpu.ops.composite import composite_vdis
    from scenery_insitu_tpu.sim import grayscott as gs

    grid = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    k = 16
    ad_iters = 2
    tf = for_dataset("gray_scott")
    cam = Camera.create((0.0, 0.6, 3.0), fov_y_deg=50.0, near=0.5, far=20.0)
    spec = slicer.make_spec(cam, (grid, grid, grid), SliceMarchConfig())
    print(f"grid={grid} spec ni={spec.ni} nj={spec.nj} chunk={spec.chunk} "
          f"dtype={spec.matmul_dtype} fold={spec.fold} "
          f"backend={jax.default_backend()}", flush=True)

    st = gs.GrayScott.init((grid, grid, grid))
    st = gs.multi_step(st, 30)
    jax.block_until_ready(st.u)
    vol = Volume.centered(st.field, extent=2.0)

    timeit(jax.jit(lambda u, v: gs.multi_step(gs.GrayScott(u, v, st.params),
                                              10).u),
           st.u, st.v, label="sim advance x10")

    # march with trivial consume: measures resample matmuls + TF + rgba prep
    def march_sum(data):
        v = Volume.centered(data, extent=2.0)
        axcam = slicer.make_axis_camera(v, cam, spec)
        def consume(c, rgba, t0, t1):
            return c + rgba.sum((0, 1))
        return slicer.slice_march(v, tf, axcam, spec, consume,
                                  jnp.zeros((spec.nj, spec.ni)))
    timeit(jax.jit(march_sum), vol.data, label="march only (sum consume)")

    # march with no TF: isolates the TF lookup cost
    def march_no_tf(data):
        v = Volume.centered(data, extent=2.0)
        axcam = slicer.make_axis_camera(v, cam, spec)
        ident = lambda val: (jnp.stack([val] * 3, -1), val * 0.3)
        def consume(c, rgba, t0, t1):
            return c + rgba.sum((0, 1))
        return slicer.slice_march(v, ident, axcam, spec, consume,
                                  jnp.zeros((spec.nj, spec.ni)))
    timeit(jax.jit(march_no_tf), vol.data, label="march, identity TF")

    # one counting pass
    def count_pass(data):
        v = Volume.centered(data, extent=2.0)
        axcam = slicer.make_axis_camera(v, cam, spec)
        thr = jnp.full((spec.nj, spec.ni), 0.1, jnp.float32)
        def consume(cst, rgba, t0, t1):
            for i in range(rgba.shape[0]):
                cst = ss.push_count(cst, thr, rgba[i])
            return cst
        return slicer.slice_march(v, tf, axcam, spec, consume,
                                  ss.init_count(spec.nj, spec.ni)).count
    timeit(jax.jit(count_pass), vol.data, label="one counting march")

    # one writing march (fixed threshold)
    def write_pass(data):
        v = Volume.centered(data, extent=2.0)
        axcam = slicer.make_axis_camera(v, cam, spec)
        thr = jnp.full((spec.nj, spec.ni), 0.1, jnp.float32)
        def consume(sst, rgba, t0, t1):
            for i in range(rgba.shape[0]):
                sst = ss.push(sst, k, thr, rgba[i], t0[i], t1[i])
            return sst
        stf = slicer.slice_march(v, tf, axcam, spec, consume,
                                 ss.init_state(k, spec.nj, spec.ni))
        return ss.finalize(stf)
    timeit(jax.jit(write_pass), vol.data, label="one writing march")

    # the fold schedules head to head: ONE write march each
    # (adaptive off -> fixed threshold, no counting pass), guarded per
    # variant so a Mosaic rejection can't kill the rest of the profile
    folds = ["xla"]
    if jax.default_backend() == "tpu":
        folds += ["pallas_seg", "pallas_fused"]
    for fname in folds:
        try:
            spec_f = slicer.make_spec(cam, (grid, grid, grid),
                                      SliceMarchConfig(fold=fname))

            def wf(data, spec_f=spec_f):
                v = Volume.centered(data, extent=2.0)
                vdi, _, _ = slicer.generate_vdi_mxu(
                    v, tf, cam, spec_f,
                    VDIConfig(max_supersegments=k, adaptive=False,
                              threshold=0.1))
                return vdi.color

            timeit(jax.jit(wf), vol.data, label=f"write march fold={fname}")
        except Exception as e:
            print(f"write march fold={fname}: FAILED "
                  f"{type(e).__name__}: {str(e)[:150]}", flush=True)

    # full VDI generation (ad_iters counting + 1 write)
    def gen(data):
        v = Volume.centered(data, extent=2.0)
        vdi, meta, _ = slicer.generate_vdi_mxu(
            v, tf, cam, spec, VDIConfig(max_supersegments=k,
                                        adaptive_iters=ad_iters))
        return vdi.color
    timeit(jax.jit(gen), vol.data, label=f"generate_vdi_mxu (ad={ad_iters})")

    # composite N=1
    def comp(color, depth):
        return composite_vdis(color[None], depth[None],
                              CompositeConfig(max_output_supersegments=k,
                                              adaptive_iters=ad_iters)).color
    vdi, _, _ = jax.jit(lambda d: slicer.generate_vdi_mxu(
        Volume.centered(d, extent=2.0), tf, cam, spec,
        VDIConfig(max_supersegments=k, adaptive_iters=ad_iters)))(vol.data)
    timeit(jax.jit(comp), vdi.color, vdi.depth, label="composite (N=1)")


if __name__ == "__main__":
    main()
