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


def four_rank_step(topo, cfg, dataset: str):
    """What lowering a four-rank temporal MXU step for the 2x2 takes, as
    a session on a TPU has it: ``(mesh, tf, spec, args, seeded, thr)`` —
    ``args`` the z-sharded field, origin, spacing and the default camera
    as shapes on the mesh, ``seeded`` the compiled threshold seeder and
    ``thr`` its output as shapes in the shardings it leaves in. The
    caller has set `jax.default_backend` to say "tpu"."""
    from scenery_insitu_tpu.core.camera import Camera
    from scenery_insitu_tpu.core.transfer import for_dataset
    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.parallel import pipeline

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
    tf = for_dataset(dataset)
    seed = pipeline.distributed_initial_threshold_mxu(mesh, tf, spec,
                                                      cfg.vdi)
    seeded = seed.lower(*args).compile()
    thr = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(seed, *args), seeded.output_shardings)
    return mesh, tf, spec, args, seeded, thr


def test_the_four_rank_frame_step_hands_its_frame_out_slot_major(
        topo, monkeypatch):
    """`vortex256-4rank`'s step program (march + fold + column exchange +
    composite, 64 planes a rank, 320 x 320, K = 16) for the 2x2 as a TPU
    builds it: after the composite one more `all-to-all` a leaf under the
    `exchange` scope, and the frame leaves as f32[4, 4|2, 320, 320] a
    rank, `P(ranks, None, None, None)`; where the ranks do not divide
    the slots, the two column exchanges alone and W-blocks."""
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.parallel import pipeline

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = FrameworkConfig().with_overrides(
        "sim.kind=vortex", f"sim.grid=[{PLANES * RANKS},{Y},{X}]",
        "slicer.engine=mxu", "vdi.adaptive_mode=temporal",
        "vdi.max_supersegments=16")
    mesh, tf, spec, args, _, thr = four_rank_step(topo, cfg, "vortex")
    on = lambda spec: NamedSharding(mesh, spec)
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


# gs1024-4rank (PR 41): 1024^3 over the 2x2, 256 planes a rank, a
# 1280 x 1280 intermediate grid, K = 16
GS_GRID, GS_OVERRIDES = (1024, 1024, 1024), (
    "sim.grid=[1024,1024,1024]", "sim.steps_per_frame=10",
    "slicer.engine=mxu", "vdi.adaptive_mode=temporal",
    "vdi.max_supersegments=16", "composite.max_output_supersegments=16",
    "runtime.dataset=gray_scott", "mesh.num_devices=4")
CHIP_BYTES = 16e9


def device_bytes(compiled) -> int:
    """What one device holds while the program runs, by the compiler's
    own account: arguments + outputs + temps."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


def test_the_1024_cube_start_is_born_in_shards(topo):
    """The program `GrayScott.init` runs for gs1024-4rank's state: every
    device writes its own 256 planes of u and v and holds nothing else —
    no index volume, no whole-grid array (8.59 GB would not fit)."""
    from scenery_insitu_tpu.sim import grayscott as gs

    mesh = Mesh(np.array(topo.devices[:RANKS]), ("ranks",))
    field = NamedSharding(mesh, P("ranks", None, None))
    small = lambda s: jax.ShapeDtypeStruct(s, jnp.int32,
                                           sharding=NamedSharding(mesh, P()))
    compiled = gs._seed_cubes(GS_GRID, field).lower(
        small((5, 3)), small((5,))).compile()
    d, h, w = GS_GRID
    m = compiled.memory_analysis()
    # u and v of one rank's 256 planes, and the tuple that names them
    assert 0 <= m.output_size_in_bytes - 2 * (d // RANKS) * h * w * 4 \
        < 4096
    assert m.argument_size_in_bytes < 4096
    assert m.temp_size_in_bytes < 4 * h * w     # under one f32 plane
    for leaf in compiled.output_shardings:
        assert leaf.is_equivalent_to(field, 3)
    assert f"f32[{d // RANKS},{h},{w}]" in compiled.as_text()
    assert f"[{d},{h},{w}]" not in compiled.as_text()


def test_the_1024_cube_sim_program_fits_a_chip(topo):
    """gs1024-4rank's sim program for the 2x2 as a TPU builds it: the
    fused stencil at tiles (16, 64) on a 256 x 1024 x 1024 shard, which
    Mosaic had never compiled, with its ring halos; under 16 GB a
    device."""
    from scenery_insitu_tpu.sim import pallas_stencil as ps

    shard = (GS_GRID[0] // RANKS,) + GS_GRID[1:]
    assert ps.schedule(shard, 10, ring=True) == (
        (("2d", 4, 16, 64, 2), ("2d", 2, 16, 64, 1)), 0)
    mesh = Mesh(np.array(topo.devices[:RANKS]), ("ranks",))
    field = jax.ShapeDtypeStruct(
        GS_GRID, jnp.float32,
        sharding=NamedSharding(mesh, P("ranks", None, None)))
    scalar = jax.ShapeDtypeStruct((), jnp.float32,
                                  sharding=NamedSharding(mesh, P()))
    compiled = ps.multi_step_pallas_sharded.lower(
        field, field, (scalar,) * 5, n=10, mesh=mesh,
        axis="ranks").compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3       # T = 4, 4, 2
    assert "collective-permute" in text
    assert device_bytes(compiled) < CHIP_BYTES
    # u and v in, u and v out, and no more than one more state of temps
    assert device_bytes(compiled) < 3.5 * 2 * 4 * np.prod(shard)


def test_the_1024_cube_step_program_fits_a_chip(topo, monkeypatch):
    """gs1024-4rank's step program (march + fold + column exchange + sort
    + composite + slot exchange at 1280 x 1280, K = 16) for the 2x2 as a
    TPU builds it: every kernel serves the new widths — nothing on the
    fallback ledger — and the program stays under 16 GB a device, beside
    the state and the sim program's output."""
    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.parallel import pipeline

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    obs.clear_ledger()
    cfg = FrameworkConfig().with_overrides(*GS_OVERRIDES)
    mesh, tf, spec, args, seeded, thr = four_rank_step(topo, cfg,
                                                       "gray_scott")
    on = lambda spec: NamedSharding(mesh, spec)
    assert (spec.ni, spec.nj) == (1280, 1280)
    compiled = pipeline.distributed_vdi_step_mxu_temporal(
        mesh, tf, spec, cfg.vdi, cfg.composite,
        reuse_tol=cfg.delta.range_tol).lower(*args, thr).compile()
    text = compiled.as_text()
    assert "sitpu_fold_fused" in text
    assert "sitpu_resegment_sorted" in text
    assert obs.ledger() == []
    state = 2 * 4 * np.prod(GS_GRID) // RANKS          # u and v, a rank
    sim_out_and_temps = 2 * state + 0.1e9
    for program in (seeded, compiled):
        # its own arguments hold the field (half the state) already
        assert (device_bytes(program) + state / 2 + sim_out_and_temps
                < CHIP_BYTES)
    (vdi, _), _ = compiled.output_shardings
    for leaf in (vdi.color, vdi.depth):
        assert leaf.is_equivalent_to(on(P("ranks", None, None, None)), 4)
    assert "f32[4,4,1280,1280]" in text


# kingsnake-u8-1chip (PR 44): 795 x 1024 x 1024 voxels of u8 on ONE chip,
# a 1280 x 1280 intermediate grid, K = 20
KS_GRID, KS_OVERRIDES = (795, 1024, 1024), (
    "slicer.engine=mxu", "vdi.adaptive_mode=temporal",
    "vdi.max_supersegments=20", "composite.max_output_supersegments=20",
    "runtime.dataset=kingsnake", "mesh.num_devices=1")


def _dataset_programs(topo, monkeypatch, grid, dtype, name):
    """A file-dataset cell's programs for one v5e as a TPU builds them:
    the occupancy ranges, the threshold seeder and the step of a session
    with `runtime.dataset=<name>` at ``grid`` / ``dtype``, K = 20.
    Returns (seeded, compiled, build, args, thr): the last three to
    compile the step of another field (`build(ranges=None)`)."""
    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.core.camera import Camera
    from scenery_insitu_tpu.core.transfer import for_dataset
    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.parallel import pipeline

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    obs.clear_ledger()
    cfg = FrameworkConfig().with_overrides(*KS_OVERRIDES,
                                           f"runtime.dataset={name}")
    mesh = Mesh(np.array(topo.devices[:1]), ("ranks",))
    on = lambda spec: NamedSharding(mesh, spec)
    like = lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype,
                                          sharding=on(P()))
    cam = Camera.create((0.0, 0.6, 3.0), fov_y_deg=50.0, near=0.3, far=20.0)
    spec = slicer.make_spec(cam, grid, cfg.slicer,
                            axis_sign=slicer.choose_axis(cam))
    assert (spec.axis, spec.sign, spec.ni, spec.nj, spec.fold) == \
        (2, -1, 1280, 1280, "pallas_fused")
    args = (jax.ShapeDtypeStruct(grid, dtype,
                                 sharding=on(P("ranks", None, None))),
            like(np.zeros(3, np.float32)),
            like(np.full(3, 2.0 / max(grid), np.float32)),
            jax.tree_util.tree_map(like, cam))
    tf = for_dataset(name)
    ranges = pipeline.distributed_volume_ranges_mxu(mesh, spec)
    ranges.lower(*args[:3]).compile()
    kept = tuple(np.zeros(s.shape, np.float32)
                 for s in jax.eval_shape(ranges, *args[:3]))
    assert kept[0].shape == (1, -(-grid[0] // 16), spec.vtiles)
    seed = pipeline.distributed_initial_threshold_mxu(mesh, tf, spec,
                                                      cfg.vdi)
    seeded = seed.lower(*args).compile()
    thr = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(seed, *args), seeded.output_shardings)

    def build(**kw):
        return pipeline.distributed_vdi_step_mxu_temporal(
            mesh, tf, spec, cfg.vdi, cfg.composite, **kw)
    compiled = build(reuse_tol=cfg.delta.range_tol, ranges=kept).lower(
        *args, thr).compile()
    return seeded, compiled, build, args, thr


def test_the_kingsnake_step_compiles_at_its_native_dtype(topo, monkeypatch):
    """The file-dataset cell's programs for one v5e as a TPU builds them:
    the occupancy ranges, the threshold seeder and the step at (795, 1024,
    1024) u8, K = 20 (not a multiple of 8), a depth of 49 chunks and 11
    planes. Mosaic takes the fold kernel at K = 20, nothing lands on the
    fallback ledger, no instruction of the step or the seeder writes an
    array as large as the volume (no widened, flipped or padded copy),
    and the step's temporaries stay under the volume's own size."""
    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.obs.profiler import hlo_large_writes

    seeded, compiled, build, args, thr = _dataset_programs(
        topo, monkeypatch, KS_GRID, jnp.uint8, "kingsnake")
    text = compiled.as_text()
    # since PR 46 the march hands the fold its value plane: no shaded
    # f32[16,4,1280,1280] chunk is written between them
    assert "sitpu_fold_fused" in text
    assert "sitpu_fold_seg_compact" not in text
    assert "f32[16,4,1280,1280]" not in text
    assert "f32[20,4,1280,1280]" in text
    assert obs.ledger() == []
    for program in (seeded, compiled):
        assert hlo_large_writes(program.as_text(), KS_GRID) == []
    volume = int(np.prod(KS_GRID))
    assert compiled.memory_analysis().temp_size_in_bytes < volume
    # the widened f32 field of the same shape: no flip and no pad either,
    # but the compiler hoists the operand's bf16 cast out of the chunk
    # loop, one `convert` of the whole volume a frame (the u8 operand's
    # cast stays inside the loop: it would only make the array larger)
    wide = (jax.ShapeDtypeStruct(KS_GRID, jnp.float32,
                                 sharding=args[0].sharding),) + args[1:]
    assert set(hlo_large_writes(
        build().lower(*wide, thr).compile().as_text(),
        KS_GRID)) <= {"convert"}


BN_GRID = (1546, 1024, 1024)


def test_the_beechnut_step_keeps_sixteen_bits_in_bf16_operands(topo,
                                                               monkeypatch):
    """`beechnut-u16-1chip` (PR 49): the same programs at (1546, 1024,
    1024) u16, a depth of 96 chunks and 10 planes, the `beechnut` tent
    as the fold kernel's immediates. EVERY convolution of the compiled
    step (the resampling matmuls: four per output row block, two byte
    planes into the first contraction and the f32 intermediate's two
    bf16 terms into the second) takes two bf16 operands, none asks for
    more than the default precision, and next to the 3.24 GB volume the
    step holds well under a tenth of it in temporaries: no plane, no
    widened copy."""
    import re

    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.obs.profiler import hlo_large_writes

    seeded, compiled, *_ = _dataset_programs(
        topo, monkeypatch, BN_GRID, jnp.uint16, "beechnut")
    text = compiled.as_text()
    assert "sitpu_fold_fused" in text and obs.ledger() == []
    for program in (seeded, compiled):
        assert hlo_large_writes(program.as_text(), BN_GRID) == []
    dtype_of = dict(re.findall(
        r"^\s*(?:ROOT )?(%[\w.-]+) = (\w+)\[", text, re.M))
    convs = re.findall(r"= f32\[[\d,]+\]\S* convolution\((%[\w.-]+), "
                       r"(%[\w.-]+)\)(.*)$", text, re.M)
    assert len(convs) >= 4 and len(convs) % 4 == 0
    for lhs, rhs, rest in convs:
        assert (dtype_of[lhs], dtype_of[rhs]) == ("bf16", "bf16")
        assert "operand_precision" not in rest
    assert " dot(" not in text
    # the intermediate's first term is taken by reduce-precision: a cast
    # to bf16 and back is excess precision to XLA, which drops it on a
    # TPU, and the second term is then 0 (read on the chip, PR 49)
    assert text.count(" reduce-precision(") >= len(convs) // 4
    volume = 2 * int(np.prod(BN_GRID))
    assert compiled.memory_analysis().argument_size_in_bytes >= volume
    assert compiled.memory_analysis().temp_size_in_bytes < volume // 8


# (K, chunk, Nj, Ni): gs512's and the dataset cells' frames (strips of
# 384 lanes: 640 = 384 + 256, 1280 = 3 x 384 + 128), gs128's and
# vortex256's (one full-row strip whose last tile is 32 / 64 lanes), and
# the one-sample chunk an occupancy-skipped iteration folds
FOLD_SHAPES = [(16, 16, 640, 640), (20, 16, 1280, 1280), (16, 16, 160, 160),
               (16, 16, 320, 320), (16, 1, 640, 640)]


@pytest.mark.parametrize("k,c,nj,ni", FOLD_SHAPES)
def test_the_fold_kernel_compiles_with_its_data_bounded_loops(topo, k, c,
                                                              nj, ni):
    """`sitpu_fold_fused` for one v5e at the cells' widths: what
    interpret mode cannot show of PR 50's phase B — Mosaic's view of
    `fori_loop`s whose bounds are scalars reduced from vectors, of the
    128-lane slices of the state (the last one narrower where the row is
    one strip), of the SMEM account, and the VMEM budget with the two
    interval planes live across phase A."""
    from scenery_insitu_tpu.core.transfer import for_dataset
    from scenery_insitu_tpu.ops import pallas_seg as psg

    one = SingleDeviceSharding(topo.devices[0])
    shape = lambda s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt,
                                                           sharding=one)
    tf = for_dataset("gray_scott")
    packed = (shape((k, 4, nj, ni)), shape((k, 2, nj, ni)),
              shape((5, nj, ni)),
              shape((2, psg.slot_tiles(nj, ni)), jnp.int32))

    def fold(packed, val, length, ratio, sk0, sk1, thr):
        return psg.fused_fold_chunk(packed, val, length, ratio, sk0, sk1,
                                    thr, max_k=k, tf=tf, interpret=False)

    plane = shape((nj, ni))
    text = jax.jit(fold, donate_argnums=0).lower(
        packed, shape((c, nj, ni)), plane, plane, shape((c,)), shape((c,)),
        plane).compile().as_text()
    assert "tpu_custom_call" in text
    assert "sitpu_fold_fused" in text
