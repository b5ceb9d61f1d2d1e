"""The vortex back-trace as a windowed Pallas TPU kernel (PR 38).

`sim/vortex.advect_window`'s windowed branch hands a rank's window of the
velocity field — its z-slab and ``H`` halo planes of each ring neighbour,
f32[3, planes + 2 H, Y, X] — and, for every point of the slab, where it
came from: the low corner's offset from the point in whole voxels and the
weight of the high corner, per axis, exactly as the XLA path computes
them. The kernel blends the eight corners WITHOUT a gather and without
the 24-wide cells the XLA path builds for one.

Per z-block it stages the block's planes and their halos in VMEM (one
DMA, the whole y and x extent: 31.5 MB at 256^2 and H = 16), then walks
tiles of 8 rows x all X lanes. For a tile it forms the blend as a sum
over INTEGER offsets of shifted copies of the staged source,

    out += wz(oz) wy(oy) wx(ox) * src[z + oz, y + oy, x + ox]

where a factor is ``1 - f`` at the point's low corner, ``f`` one above it
and 0 elsewhere — so exactly two per axis are non-zero, the same eight
corners and the same products ``(wz wy) wx`` as the XLA blend, summed in
another order (agreement to a few ulp, not to the bit). The loops run
over the TILE's own range of offsets (its min and max per axis, computed
outside and read from SMEM), so a tile in quiet flow does the minimal
2 x 2 x 2 and only tiles through a ring's core pay for their spread.
Everything is dense vector work: a plane index on an untiled dimension,
aligned loads of two row groups and a sublane rotate for y (y wraps by
index arithmetic), a lane rotate over the full X extent for x (which IS
the periodic wrap), compares and multiply-adds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from scenery_insitu_tpu.ops.pallas_util import TILE_H, TILE_W

# planes of the slab per grid step; the staged block is TZ + 2 H planes
TZ = 8
# v5e: 128 MiB of VMEM; the staged block, the tiles' operands and the
# output, double-buffered, come to ~70 MB at 256^2 and H = 16
_VMEM_LIMIT = 100 * 1024 * 1024


def fits(planes: int, halo: int, y: int, x: int) -> bool:
    """Can the kernel take a slab of this shape? Whole tiles in y and x,
    whole z-blocks, and a staged block inside the VMEM limit."""
    staged = 3 * (TZ + 2 * halo) * y * x * 4
    blocks = 2 * 9 * TZ * y * x * 4
    return (planes % TZ == 0 and y % TILE_H == 0 and x % TILE_W == 0
            and staged + blocks <= _VMEM_LIMIT - (8 << 20))


def _kernel(halo, ranges, win, rz, ry, rx, fz, fy, fx, out, staged, sem):
    zb = pl.program_id(0)
    _, tz, ny, nx = out.shape
    row_tiles = ny // TILE_H
    copy = pltpu.make_async_copy(
        win.at[:, pl.ds(zb * tz, tz + 2 * halo)], staged, sem)
    copy.start()
    copy.wait()
    rows = jax.lax.broadcasted_iota(jnp.int32, (TILE_H, nx), 0)

    def corner_weight(low, frac, offset):
        return jnp.where(low == offset, 1.0 - frac,
                         jnp.where(low + 1 == offset, frac, 0.0))

    def tile(t, carry):
        k, j = t // row_tiles, t % row_tiles
        at = pl.ds(pl.multiple_of(j * TILE_H, TILE_H), TILE_H)
        low = [r[k, at, :] for r in (rz, ry, rx)]
        frac = [f[k, at, :] for f in (fz, fy, fx)]
        base = ((zb * tz + k) * row_tiles + j) * 6
        (z_lo, z_hi, y_lo, y_hi, x_lo, x_hi) = [
            ranges[base + i] for i in range(6)]

        def over_z(oz, acc):
            wz = corner_weight(low[0], frac[0], oz)
            plane = k + halo + oz

            def over_y(oy, acc):
                wzy = wz * corner_weight(low[1], frac[1], oy)
                # rows j * 8 + oy ... + 8 of the plane, y wrapping: two
                # aligned groups of 8, rotated and spliced
                start = jax.lax.rem(j * TILE_H + oy + ny, ny)
                shift = jax.lax.rem(start, TILE_H)
                first = pl.multiple_of(start - shift, TILE_H)
                second = pl.multiple_of(
                    jax.lax.rem(first + TILE_H, ny), TILE_H)
                back = jax.lax.rem(TILE_H - shift, TILE_H)
                src = [jnp.where(
                    rows < TILE_H - shift,
                    pltpu.roll(staged[c, plane, pl.ds(first, TILE_H), :],
                               back, 0),
                    pltpu.roll(staged[c, plane, pl.ds(second, TILE_H), :],
                               back, 0)) for c in range(3)]

                def over_x(ox, acc):
                    w = wzy * corner_weight(low[2], frac[2], ox)
                    # out[x] takes src[x + ox]; the rotate over all X
                    # lanes is the periodic wrap
                    turn = jax.lax.rem(nx - ox, nx)
                    return tuple(a + w * pltpu.roll(s, turn, 1)
                                 for a, s in zip(acc, src))

                return jax.lax.fori_loop(x_lo, x_hi + 1, over_x, acc)

            return jax.lax.fori_loop(y_lo, y_hi + 1, over_y, acc)

        zero = jnp.zeros((TILE_H, nx), jnp.float32)
        acc = jax.lax.fori_loop(z_lo, z_hi + 1, over_z, (zero,) * 3)
        for c in range(3):
            out[c, k, at, :] = acc[c]
        return carry

    jax.lax.fori_loop(0, tz * row_tiles, tile, 0)


def relative(low, halo: int):
    """The XLA path's low corners as whole-voxel offsets from the point
    itself: ``low`` is (the plane of the WINDOW, the row and the column
    of the grid padded by one wrap layer: `sim/vortex._back_trace`); y
    and x come wrapped into [-n/2, n/2)."""
    z0, y0, x0 = low
    _, ny, nx = z0.shape
    k, j, i = (jax.lax.broadcasted_iota(jnp.int32, z0.shape, a)
               for a in range(3))
    return (z0 - (k + halo),
            jnp.mod(y0 - 1 - j + ny // 2, ny) - ny // 2,
            jnp.mod(x0 - 1 - i + nx // 2, nx) - nx // 2)


def tile_ranges(rel):
    """int32[planes * Y / 8 * 6]: per tile of 8 rows the smallest low
    corner's offset and the largest high corner's, for z, y, x."""
    out = []
    for r in rel:
        planes, ny, nx = r.shape
        tiles = r.reshape(planes, ny // TILE_H, TILE_H * nx)
        out += [tiles.min(-1), tiles.max(-1) + 1]
    return jnp.stack(out, -1).reshape(-1)


@functools.partial(jax.jit, static_argnames=("halo", "interpret"))
def back_trace(window, low, frac, halo: int, interpret: bool = False):
    """The advected slab f32[3, planes, Y, X] from ``window``
    f32[3, planes + 2 halo, Y, X], the points' low corners ``low`` (as
    `relative` takes them) and high-corner weights ``frac``, each three
    [planes, Y, X] arrays in the order z, y, x."""
    _, deep, ny, nx = window.shape
    planes = deep - 2 * halo
    if planes % TZ or ny % TILE_H or nx % TILE_W:
        raise ValueError(
            f"a slab of {planes} x {ny} x {nx} is not whole z-blocks of "
            f"{TZ} planes and tiles of {TILE_H} x {TILE_W}: ask `fits`")
    rel = relative(low, halo)
    point = pl.BlockSpec((TZ, ny, nx), lambda i, ranges: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, halo),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(planes // TZ,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] + [point] * 6,
            out_specs=pl.BlockSpec((3, TZ, ny, nx),
                                   lambda i, ranges: (0, i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((3, TZ + 2 * halo, ny, nx), jnp.float32),
                pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((3, planes, ny, nx), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="sitpu_sim_advect_window",
    )(tile_ranges(rel), window, *rel, *frac)
