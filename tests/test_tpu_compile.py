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
