"""Compiled for the v5e WITHOUT a chip (the TPU compiler is installed and
compiles for a described topology): what interpret mode cannot show —
Mosaic's own refusals (tiling, VMEM) of the vortex back-trace kernel at
the benchmark cell's real widths, and the four-rank frame program around
it with its `cond`, halo permutes and fallback. Nothing runs, so nothing
here says anything about results or times.

One file only, the topology described inside a fixture: a process keeps
the TPU library's lock until it exits, and each xdist worker imports
every test file."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from scenery_insitu_tpu.sim import pallas_backtrace
from scenery_insitu_tpu.sim import vortex as vx

# vortex256-4rank: 64 planes a rank, 16 halo planes, 256 x 256
PLANES, HALO, Y, X, RANKS = 64, 16, 256, 256, 4


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry compiled for a described chip cannot be read back without
    # one: keep these compiles out of the persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_the_windowed_kernel_compiles_at_the_cells_widths(topo):
    one = SingleDeviceSharding(topo.devices[0])
    shape = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)
    point = (PLANES, Y, X)
    assert pallas_backtrace.fits(PLANES, HALO, Y, X)
    compiled = pallas_backtrace.back_trace.lower(
        shape((3, PLANES + 2 * HALO, Y, X), jnp.float32),
        (shape(point, jnp.int32),) * 3, (shape(point, jnp.float32),) * 3,
        halo=HALO, interpret=False).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "sitpu_sim_advect_window" in text
    assert " gather(" not in text


def test_the_four_rank_frame_program_compiles_with_both_branches(
        topo, monkeypatch):
    """The cell's sim program for the 2x2 as a TPU builds it: the kernel
    in the windowed branch, the all-gather and the one gather only in
    `whole_field`, both under the `cond` and under `sim_advect`."""
    monkeypatch.setattr(vx, "_window_kernel", pallas_backtrace.fits)
    monkeypatch.setattr(vx, "should_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices[:RANKS]), ("ranks",))
    rep = NamedSharding(mesh, P())
    u = jax.ShapeDtypeStruct(
        (3, PLANES * RANKS, Y, X), jnp.float32,
        sharding=NamedSharding(mesh, P(None, "ranks", None, None)))
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    compiled = vx.frame_program(mesh, "ranks").lower(
        u, vx.VortexParams(scalar, scalar), 1).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_vortex_frame")
    cond = re.findall(r"conditional\(.*op_name=\"([^\"]*)\"", text)
    assert len(cond) == 1 and "sitpu_sim_advect" in cond[0]
    assert text.count("tpu_custom_call") == 1
    assert len(re.findall(r" all-gather(-start)?\(", text)) == 1
    assert len(re.findall(r" gather\(", text)) == 1
    assert "collective-permute" in text
    # the fallback's 24-wide cells are the program's temp, window or not
    assert compiled.memory_analysis().temp_size_in_bytes < 8e9


def test_the_four_rank_frame_step_hands_its_frame_out_slot_major(
        topo, monkeypatch):
    """`vortex256-4rank`'s step program (march + fold + column exchange +
    composite, 64 planes a rank, 320 x 320, K = 16) for the 2x2 as a TPU
    builds it: after the composite one more `all-to-all` a leaf under the
    `exchange` scope, and the frame leaves as f32[4, 4|2, 320, 320] a
    rank, `P(ranks, None, None, None)`; where the ranks do not divide
    the slots, the two column exchanges alone and W-blocks."""
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.core.camera import Camera
    from scenery_insitu_tpu.core.transfer import for_dataset
    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.parallel import pipeline

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = FrameworkConfig().with_overrides(
        "sim.kind=vortex", f"sim.grid=[{PLANES * RANKS},{Y},{X}]",
        "slicer.engine=mxu", "vdi.adaptive_mode=temporal",
        "vdi.max_supersegments=16")
    mesh = Mesh(np.array(topo.devices[:RANKS]), ("ranks",))
    on = lambda spec: NamedSharding(mesh, spec)
    like = lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype,
                                          sharding=on(P()))
    cam = Camera.create((0.0, 0.6, 3.0), fov_y_deg=50.0, near=0.3, far=20.0)
    grid = tuple(cfg.sim.grid)
    spec = slicer.make_spec(cam, grid, cfg.slicer,
                            axis_sign=slicer.choose_axis(cam),
                            multiple_of=RANKS)
    args = (jax.ShapeDtypeStruct(grid, jnp.float32,
                                 sharding=on(P("ranks", None, None))),
            like(np.zeros(3, np.float32)),
            like(np.full(3, 2.0 / max(grid), np.float32)),
            jax.tree_util.tree_map(like, cam))
    tf = for_dataset("vortex")
    seed = pipeline.distributed_initial_threshold_mxu(mesh, tf, spec,
                                                      cfg.vdi)
    thr = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(seed, *args),
        seed.lower(*args).compile().output_shardings)
    for k_out, slot_major in ((16, True), (6, False)):
        comp = cfg.composite.__class__(max_output_supersegments=k_out)
        compiled = pipeline.distributed_vdi_step_mxu_temporal(
            mesh, tf, spec, cfg.vdi, comp).lower(*args, thr).compile()
        text = compiled.as_text()
        a2a = re.findall(r"= (\S+) all-to-all\(.*op_name=\"([^\"]*)\"",
                         text)
        assert all("sitpu_exchange" in name for _, name in a2a)
        (vdi, _), _ = compiled.output_shardings
        want = (P("ranks", None, None, None) if slot_major
                else P(None, None, None, "ranks"))
        for leaf in (vdi.color, vdi.depth):
            assert leaf.is_equivalent_to(on(want), 4)
        assert len(a2a) == (4 if slot_major else 2), a2a
        out = spec.ni // RANKS
        if slot_major:
            assert f"f32[{k_out // RANKS},4,{spec.nj},{spec.ni}]" in text
            # what crosses the ICI is the unpadded H-minor block
            assert sum(f"[{k_out},4,{spec.nj},{out}]" in shape
                       or f"[{k_out},2,{spec.nj},{out}]" in shape
                       for shape, _ in a2a) == 2
