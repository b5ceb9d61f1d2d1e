"""Pallas TPU kernel for the Gray-Scott reaction-diffusion step.

The XLA formulation (sim/grayscott.py) builds the 6-point Laplacian from
``jnp.roll`` — twelve materialized full-volume copies per step. This
kernel fuses ``T`` whole steps into one pass over (z × h) tiles: each
grid step assembles a ``[tz + 2T, th + 16, W]`` padded block of u and v
in VMEM scratch (the tile plus a T-slice z halo and an 8-row h halo on
each side, taken from neighbor views of the same HBM arrays with
periodic wrap in the BlockSpec index_map), advances it T times entirely
in VMEM — one z-plane at a time, ping-ponging between two scratch
copies; z neighbors are the adjacent planes, h and w neighbors are
sublane/lane rotates of the plane — and writes the central tile once.
Halo validity shrinks by one slice/row per step, so after T steps the
central ``tz × th`` tile is exact. Per T steps the volume is read
``(tz+2T)(th+16)/(tz·th)`` times and written once.

The h halo is 8 rows whatever T is: Mosaic requires the second-minor
block dimension to be a multiple of the 8-sublane tile (a ``(tz, T, W)``
halo view is refused by the lowering), and whole tiles keep every
in-kernel copy aligned. ``th == H`` is the z-slab special case (the h
"halo" is the periodic wrap itself). W stays whole: it is the lane axis
and truly periodic, so the rotate is exact.

The plane loop keeps the compiled program small (one plane of straight-
line vector code per step instead of the whole block unrolled) and the
VMEM footprint explicit: scratch + double-buffered blocks are counted by
`_vmem_bytes` and requested through ``vmem_limit_bytes``; nothing rides
on Mosaic's 16 MiB default scoped limit.

A frame's ``n`` steps are a static walk over `schedule` (n = 10: two T=4
passes and one T=2): `_multi_step_impl` calls the kernel once per pass
and hands each pass's u and v straight to the next, so the compiled sim
program is those kernels and no whole-grid copy between them.

A field that is z-sharded over the ranks of a mesh runs the same kernel
on every rank's shard (`multi_step_pallas_sharded`, under ``shard_map``).
The periodic wrap of the BlockSpec is per buffer, which at a shard's two
ends is the wrong neighbour: there the kernel's first and last z-block
take their outer T planes from two more operands per field, the ring
neighbours' boundary planes, which each pass fetches by ``ppermute``
before its kernel (T planes of u and v each way, nothing the size of a
shard). With no such operands (one device) the kernel, its BlockSpecs and
its tiles are what they were before shards were served.

On CPU the kernel runs in interpret mode (used by the parity tests); the
production CPU path stays on the XLA formulation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# h halo rows on each side: one f32 sublane tile (see module docstring)
_HALO_H = 8
# steps fused per pass; the h halo bounds it (validity shrinks 1 row/step)
_FUSE_T = 4
# Scoped VMEM requested for the kernel, of the 128 MiB a v5e core has
# (Mosaic's default scoped limit is 16 MiB). `_vmem_bytes` screens tiles
# against it.
_VMEM_LIMIT = 100 * 1024 * 1024


def _vmem_bytes(tz: int, th: int, t: int, w: int,
                ring: bool = False) -> int:
    """VMEM the fused kernel holds at tile (tz, th): the ping-pong scratch
    copies of padded u and v, the double-buffered input views (center,
    z halos, h halos, corners; with ``ring`` also the neighbours' z
    halos and their corners) and output tiles, plus a margin of 24
    planes for the per-plane update's temporaries. Without the margin
    this is Mosaic's own accounting (libtpu 0.0.34 compiling for v5e, at
    512^3): T=4 (32, 64) is 66.0 MiB here and 66.02 MiB allocated there;
    T=1 (32, 128) is 89.4 MiB here and 89.38 MiB there. With ``ring``
    (a 128 x 512 x 512 shard, compiled for four v5e): T=4 (32, 64) is
    71.0 MiB here and 71.00 MiB there."""
    hh = _HALO_H
    thp = th + 2 * hh
    scratch = (4 if t > 1 else 2) * (tz + 2 * t) * thp
    blocks = 2 * 2 * (tz * th + 2 * t * th + 2 * tz * hh + 4 * t * hh)
    if ring:
        blocks += 2 * 2 * 2 * t * thp
    outs = 2 * 2 * tz * th
    temps = 24 * thp
    return (scratch + blocks + outs + temps) * w * 4


def _read_amp(tz: int, th: int, t: int) -> float:
    """Volume reads per T-step pass, in units of the volume."""
    return (tz + 2 * t) * (th + 2 * _HALO_H) / (tz * th)


def _tiles(shape, t: int, ring: bool = False):
    """Every (traffic, tz, th) tile that satisfies the lattice
    (T | tz | D, 8 | th | H) and fits `_VMEM_LIMIT`, cheapest first by
    modeled HBM traffic per step. ``ring``: ``shape`` is one rank's
    shard of a z-sharded field, whose kernel also holds the neighbours'
    halo blocks."""
    d, h, w = shape
    out = []
    for tz in (64, 32, 16, 8, 4, 2, 1):
        if d % tz or tz % t:
            continue
        for th in sorted({h, 256, 128, 64, 32, 16, 8}, reverse=True):
            if th > h or h % th or th % _HALO_H:
                continue
            if _vmem_bytes(tz, th, t, w, ring) > _VMEM_LIMIT:
                continue
            out.append(((_read_amp(tz, th, t) + 1.0) / t, tz, th))
    out.sort()
    return out


def tz_candidates(shape, t_steps: int = 1) -> tuple:
    """z-slab sizes (th == H) that fit, cheapest first."""
    h = shape[1]
    return tuple(tz for _, tz, th in _tiles(shape, t_steps) if th == h)


def pick_tz(shape, t_steps: int = 1) -> int:
    """Best fitting z-slab size (0 = none fits)."""
    cands = tz_candidates(shape, t_steps)
    return cands[0] if cands else 0


def tile2d_candidates(shape, t_steps: int = 1) -> tuple:
    """(tz, th) tiles with th < H that fit, cheapest first."""
    h = shape[1]
    return tuple((tz, th) for _, tz, th in _tiles(shape, t_steps)
                 if th < h)


def _best_schedule(shape, t: int, ring: bool = False):
    """The cheapest fitting tile for a T-step pass by modeled HBM traffic:
    ("2d", tz, th), ("1d", tz, H) when the whole-H slab wins, or None when
    no tile satisfies the lattice and the VMEM limit. Deterministic in
    the shape — what Mosaic then says about it reaches the caller."""
    tiles = _tiles(shape, t, ring)
    if not tiles:
        return None
    _, tz, th = tiles[0]
    return ("1d" if th == shape[1] else "2d"), tz, th


def fused_supported(shape, t_steps: int = 1, ring: bool = False) -> bool:
    """Does some tile of the fused kernel fit this grid (with ``ring``:
    this shard of a z-sharded grid)? W must fill whole 128-lane tiles
    for the periodic lane rotate. A tile is at least ``t_steps`` planes
    deep (T | tz | D), so a shard that has one holds the T planes its
    ring neighbours take from it."""
    return shape[2] % 128 == 0 and bool(_tiles(shape, t_steps, ring))


def _kernel(t, tz, th, with_ranges, ring, p_ref, *refs):
    # per field: center, n, s, w, e, nw, ne, sw, se and, with `ring`,
    # the neighbours' z halos (nw, n, ne of the rank before; sw, s, se
    # of the rank after)
    nv = 15 if ring else 9
    u_in, v_in, (uo_ref, vo_ref, *rest) = refs[:nv], refs[nv:2 * nv], \
        refs[2 * nv:]
    if with_ranges:
        vlo_ref, vhi_ref, *rest = rest
    # (u, v) scratch pairs: one for T == 1, two to ping-pong between
    pairs = [rest[i:i + 2] for i in range(0, len(rest), 2)]
    f, k, du, dv, dt = (p_ref[i] for i in range(5))
    hh = _HALO_H
    thp = th + 2 * hh
    tzp = tz + 2 * t
    w = u_in[0].shape[-1]

    def assemble(dst, c, n, s, w_, e, nw, ne, sw, se, *ring_halos):
        def rows(z, west, mid, east):
            dst[z, 0:hh] = west
            dst[z, hh:hh + th] = mid
            dst[z, hh + th:thp] = east

        def center(i, _):
            rows(t + i, w_[i], c[i], e[i])
            return 0

        def halo(z0, west, mid, east):
            for i in range(t):
                rows(z0 + i, west[i], mid[i], east[i])

        jax.lax.fori_loop(0, tz, center, 0)
        if not ring_halos:
            halo(0, nw, n, ne)
            halo(t + tz, sw, s, se)
            return
        # a shard's first z-block continues north into the rank before,
        # its last one south into the rank after; between them the
        # buffer's own planes are the neighbours, as on one device
        rnw, rn, rne, rsw, rs, rse = ring_halos
        first = pl.program_id(0) == 0
        last = pl.program_id(0) == pl.num_programs(0) - 1
        pl.when(first)(lambda: halo(0, rnw, rn, rne))
        pl.when(jnp.logical_not(first))(lambda: halo(0, nw, n, ne))
        pl.when(last)(lambda: halo(t + tz, rsw, rs, rse))
        pl.when(jnp.logical_not(last))(lambda: halo(t + tz, sw, s, se))

    assemble(pairs[0][0], *u_in)
    assemble(pairs[0][1], *v_in)

    def lap(src, i, x):
        # h and w neighbors by rotate: w is truly periodic; the h wrap
        # only pollutes the outermost halo rows, which the shrinking
        # validity discards anyway
        return (src[i - 1] + src[i + 1]
                + pltpu.roll(x, 1, 0) + pltpu.roll(x, thp - 1, 0)
                + pltpu.roll(x, 1, 1) + pltpu.roll(x, w - 1, 1) - 6.0 * x)

    def update(su, sv, i):
        u = su[i]
        v = sv[i]
        uvv = u * v * v
        return (u + dt * (du * lap(su, i, u) - uvv + f * (1.0 - u)),
                v + dt * (dv * lap(sv, i, v) + uvv - (f + k) * v))

    for s in range(t - 1):
        su, sv = pairs[s % 2]
        nu, nv = pairs[(s + 1) % 2]

        def plane(i, _, su=su, sv=sv, nu=nu, nv=nv):
            nu[i], nv[i] = update(su, sv, i)
            return 0

        jax.lax.fori_loop(s + 1, tzp - 1 - s, plane, 0)

    su, sv = pairs[(t - 1) % 2]

    def last(i, carry):
        un_, vn_ = update(su, sv, i)
        vout = vn_[hh:hh + th]
        uo_ref[i - t] = un_[hh:hh + th]
        vo_ref[i - t] = vout
        if not with_ranges:
            return carry
        return (jnp.minimum(carry[0], jnp.min(vout)),
                jnp.maximum(carry[1], jnp.max(vout)))

    init = ((jnp.float32(jnp.inf), jnp.float32(-jnp.inf)) if with_ranges
            else 0)
    rng = jax.lax.fori_loop(t, t + tz, last, init)
    if with_ranges:
        # occupancy epilogue: per-tile min/max of the RENDERED field (v)
        # ride out of the pass — the tile is already in VMEM, so the
        # ranges cost no extra HBM traffic
        i, j = pl.program_id(0), pl.program_id(1)
        vlo_ref[i, j] = rng[0]
        vhi_ref[i, j] = rng[1]


def _fused_call(u, v, params_vec, t: int, tz: int, th: int,
                interpret: bool, with_ranges: bool, ring_halos: tuple = ()):
    """One T-step pass over ``u``, ``v`` f32[D, H, W]. The z halo of the
    first and last z-block is the buffer's own periodic wrap, or, with
    ``ring_halos = (u_north, u_south, v_north, v_south)`` (f32[T, H, W]:
    the T planes before plane 0 and after plane D-1), those planes."""
    d, h, w = u.shape
    hh = _HALO_H
    if t > hh:
        raise ValueError(f"t_steps={t} exceeds the {hh}-row h halo")
    # a tile off the lattice makes grid=(d//tz, h//th) floor-divide and
    # silently leaves part of the output unwritten
    if d % tz or tz % t or h % th or th % hh:
        raise ValueError(
            f"tile (tz={tz}, th={th}) violates T | tz | D and {hh} | th | H "
            f"for grid {u.shape} at T={t}")
    nzb, nhb = d // tz, h // th
    nz_t, nh_8 = d // t, h // hh      # array length in halo-block units
    rz, rh = tz // t, th // hh

    zm = lambda i: (i * rz - 1) % nz_t
    zp = lambda i: (i + 1) * rz % nz_t
    hm = lambda j: (j * rh - 1) % nh_8
    hp = lambda j: (j + 1) * rh % nh_8
    c_ = pl.BlockSpec((tz, th, w), lambda i, j: (i, j, 0))
    # halo views in halo-block units (periodic wrap by modular index)
    n_ = pl.BlockSpec((t, th, w), lambda i, j: (zm(i), j, 0))
    s_ = pl.BlockSpec((t, th, w), lambda i, j: (zp(i), j, 0))
    w_ = pl.BlockSpec((tz, hh, w), lambda i, j: (i, hm(j), 0))
    e_ = pl.BlockSpec((tz, hh, w), lambda i, j: (i, hp(j), 0))
    nw = pl.BlockSpec((t, hh, w), lambda i, j: (zm(i), hm(j), 0))
    ne = pl.BlockSpec((t, hh, w), lambda i, j: (zm(i), hp(j), 0))
    sw = pl.BlockSpec((t, hh, w), lambda i, j: (zp(i), hm(j), 0))
    se = pl.BlockSpec((t, hh, w), lambda i, j: (zp(i), hp(j), 0))
    specs = [c_, n_, s_, w_, e_, nw, ne, sw, se]
    fields = [[u] * 9, [v] * 9]
    if ring_halos:
        # the neighbours' planes are read by one row of z-blocks each
        # (the first, the last). Off it the index holds still, at the
        # block that row ends or starts with, so that nothing is fetched
        # for the blocks in between
        jn = lambda i, j: jnp.where(i == 0, j, nhb - 1)
        js = lambda i, j: jnp.where(i == nzb - 1, j, 0)
        for jr in (jn, js):
            specs += [
                pl.BlockSpec((t, hh, w),
                             lambda i, j, jr=jr: (0, hm(jr(i, j)), 0)),
                pl.BlockSpec((t, th, w),
                             lambda i, j, jr=jr: (0, jr(i, j), 0)),
                pl.BlockSpec((t, hh, w),
                             lambda i, j, jr=jr: (0, hp(jr(i, j)), 0))]
        un, us, vn, vs = ring_halos
        fields[0] += [un] * 3 + [us] * 3
        fields[1] += [vn] * 3 + [vs] * 3

    out_specs = [c_, c_]
    out_shape = [jax.ShapeDtypeStruct((d, h, w), jnp.float32)] * 2
    if with_ranges:
        # whole [nzb, nhb] arrays resident in SMEM across the (sequential)
        # grid — Mosaic refuses (1, 1) blocks of an SMEM array
        rng = pl.BlockSpec(memory_space=pltpu.SMEM)
        out_specs += [rng, rng]
        out_shape += [jax.ShapeDtypeStruct((nzb, nhb), jnp.float32)] * 2
    pad = pltpu.VMEM((tz + 2 * t, th + 2 * hh, w), jnp.float32)
    return pl.pallas_call(
        functools.partial(_kernel, t, tz, th, with_ranges, bool(ring_halos)),
        grid=(nzb, nhb),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + specs + specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pad] * (4 if t > 1 else 2),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=f"gray_scott_fused_t{t}",
    )(params_vec, *fields[0], *fields[1])


@functools.partial(jax.jit, static_argnames=("t_steps", "interpret", "tz",
                                             "with_ranges"))
def step_pallas(u: jnp.ndarray, v: jnp.ndarray, params_vec: jnp.ndarray,
                t_steps: int = 1, interpret: bool = False, tz: int = 0,
                with_ranges: bool = False):
    """Advance ``t_steps`` Gray-Scott steps in one fused pass over whole-H
    z slabs. ``params_vec = [f, k, du, dv, dt]`` (f32[5]).

    ``tz=0`` takes `pick_tz`; an explicit ``tz`` must satisfy
    ``t_steps | tz | D``. Either way the tile goes to Mosaic as is: a
    compiler refusal reaches the caller at compile time.

    ``with_ranges=True`` appends the occupancy epilogue (ops/occupancy):
    the return becomes ``(u', v', vlo, vhi)`` with per-z-slab min/max of
    the updated v field shaped ``[d // tz, 1]`` — DATA-layout brick
    ranges at the kernel's own granularity, normalized downstream by
    `occupancy.remap_ranges`."""
    t = t_steps
    tz = tz or pick_tz(u.shape, t)
    if not tz:
        raise ValueError(
            f"no z slab of grid {u.shape} fits the VMEM limit at T={t}")
    return _fused_call(u, v, params_vec, t, tz, u.shape[1], interpret,
                       with_ranges)


@functools.partial(jax.jit,
                   static_argnames=("t_steps", "interpret", "tz", "th",
                                    "with_ranges"))
def step_pallas2d(u, v, params_vec, t_steps: int = 1,
                  interpret: bool = False, tz: int = 0, th: int = 0,
                  with_ranges: bool = False):
    """Advance ``t_steps`` steps in one 2D-blocked (z × h) fused pass.

    ``(0, 0)`` takes the cheapest `tile2d_candidates` tile; an explicit
    ``(tz, th)`` must satisfy ``T | tz | D`` and ``8 | th | H``. The tile
    goes to Mosaic as is (see `step_pallas`).

    ``with_ranges=True`` appends the occupancy epilogue: the return
    becomes ``(u', v', vlo, vhi)`` with per-(z, y)-tile min/max of the
    updated v shaped ``[d // tz, h // th]`` (see `step_pallas`)."""
    t = t_steps
    if bool(tz) != bool(th):
        raise ValueError("pass both tz and th (or neither)")
    if not tz:
        cands = tile2d_candidates(u.shape, t)
        if not cands:
            raise ValueError(
                f"no (tz, th) tile of grid {u.shape} fits the VMEM limit "
                f"at T={t}")
        tz, th = cands[0]
    return _fused_call(u, v, params_vec, t, tz, th, interpret, with_ranges)


def schedule(shape, n: int, ring: bool = False) -> tuple:
    """The passes ``n`` steps decompose into on ``shape`` (with ``ring``:
    on this shard of a z-sharded grid), greedily by fusion factor (n=10
    runs two T=4 passes and one T=2 pass instead of degrading the whole
    loop to a smaller T): ``((kind, T, tz, th, reps), ...)``, plus the
    steps no fused tile covers (0 whenever `fused_supported`)."""
    out = []
    remaining = n
    for t in range(min(_FUSE_T, n), 0, -1):
        reps = remaining // t
        sched = _best_schedule(shape, t, ring) if reps else None
        if sched is None:
            continue
        out.append((sched[0], t, sched[1], sched[2], reps))
        remaining -= reps * t
    return tuple(out), remaining


def _ring_halos(u, v, t: int, axis) -> tuple:
    """`_fused_call`'s ``ring_halos`` of this rank's shard of a field
    that is z-sharded over ``axis``: the last ``t`` planes of the rank
    before and the first ``t`` of the rank after, in ring order, which
    is what makes the global field periodic in z as the roll formulation
    is. Runs inside ``shard_map`` (the idiom of
    `parallel.mesh.halo_exchange_z`, without its edge clamp)."""
    n = jax.lax.axis_size(axis)
    up = [(r, (r + 1) % n) for r in range(n)]
    down = [(r, (r - 1) % n) for r in range(n)]
    return tuple(h for x in (u, v)
                 for h in (jax.lax.ppermute(x[-t:], axis, up),
                           jax.lax.ppermute(x[:t], axis, down)))


def _multi_step_impl(u, v, params_vec, n: int, interpret: bool,
                     ranges_to, axis=None):
    """The `schedule` walk shared by `multi_step_pallas`,
    `multi_step_pallas_sharded` and `multi_step_pallas_ranges`: a static
    walk, one `_fused_call` per scheduled pass, each pass's outputs
    handed straight to the next. The schedule is static (``n`` is a
    static argument, ``reps`` are Python ints), so the compiled program
    is the ``sum(reps)`` kernels and nothing between them. Not a
    ``fori_loop`` per pass: XLA copies each kernel's whole-grid results
    into the loop's carry buffers (u and v once per trip: six 537 MB
    copies per frame at 512^3, n = 10).

    ``axis`` (inside ``shard_map``): u and v are this rank's shard of a
    field z-sharded over that mesh axis, and every pass takes its outer
    z halo from the ring neighbours (`_ring_halos`): between the kernels
    there are then the permutes of T planes, and still nothing that
    writes a shard.

    ``ranges_to = (nzb, nyb)`` threads the occupancy epilogue through
    every pass; the LAST executed pass's ranges describe the final
    field, which is what the caller gets, normalized from the kernel's
    native granularity onto the fixed (nzb, nyb) brick grid
    (occupancy.remap_ranges)."""
    with_ranges = ranges_to is not None
    ring = axis is not None
    if with_ranges:
        from scenery_insitu_tpu.ops.occupancy import (field_ranges,
                                                      remap_ranges)
        if n == 0:
            # no pass runs to produce ranges — reduce the field as-is
            # (the render-only sim_steps=0 A/B)
            r = field_ranges(v, *ranges_to)
            return (u, v, r.lo, r.hi)
    passes, remaining = schedule(u.shape, n, ring)
    if remaining:   # fused_supported(shape) is False: caller should gate
        raise ValueError(f"no fused-stencil tile fits grid {u.shape}")
    for _, t, tz, th, reps in passes:
        for _ in range(reps):
            halos = _ring_halos(u, v, t, axis) if ring else ()
            u, v, *rng = _fused_call(u, v, params_vec, t, tz, th, interpret,
                                     with_ranges, halos)
    if with_ranges:
        return (u, v) + remap_ranges(*rng, ranges_to)
    return (u, v)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def multi_step_pallas(u, v, params_vec, n: int, interpret: bool = False):
    """n Gray-Scott steps in the passes `schedule` gives."""
    return _multi_step_impl(u, v, params_vec, n, interpret, None)


@functools.partial(jax.jit, static_argnames=("n", "mesh", "axis",
                                             "interpret"))
def multi_step_pallas_sharded(u, v, params, n: int, mesh, axis,
                              interpret: bool = False):
    """`multi_step_pallas` of a field z-sharded over ``axis`` of ``mesh``
    (``NamedSharding(mesh, P(axis, None, None))``, which u' and v' keep):
    each rank runs the scheduled kernels on its own shard and takes every
    pass's outer z halo from its ring neighbours. ``params`` are the five
    scalars ``(f, k, du, dv, dt)`` themselves: stacked here, inside the
    program, because on a mesh each eager op of the host's own stack is
    a launch on every device (3.5 of a 51 ms frame on four v5e chips)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(axis, None, None)
    return shard_map(
        lambda u, v, p: _multi_step_impl(u, v, p, n, interpret, None, axis),
        mesh=mesh, in_specs=(spec, spec, P()), out_specs=(spec, spec),
        check_vma=False)(u, v, jnp.stack(params))


@functools.partial(jax.jit, static_argnames=("n", "nzb", "nyb",
                                             "interpret"))
def multi_step_pallas_ranges(u, v, params_vec, n: int, nzb: int, nyb: int,
                             interpret: bool = False):
    """`multi_step_pallas` with the occupancy epilogue: returns
    ``(u', v', vlo, vhi)`` where vlo/vhi are per-brick min/max of the
    FINAL v field on the (nzb, nyb) data-layout brick grid
    (ops/occupancy.FieldRanges arrays) — the per-frame empty-space
    structure rides out of the sim pass instead of costing a volume
    sweep."""
    return _multi_step_impl(u, v, params_vec, n, interpret, (nzb, nyb))


def ring_halo_traffic(shape, n: int) -> tuple:
    """What ``n`` steps on ``shape``, one rank's shard of a z-sharded
    field, exchange with the ring neighbours: ``(exchanges, bytes)``, one
    exchange per scheduled pass, in which the rank sends (and receives)
    T planes of u and of v each way."""
    passes, _ = schedule(shape, n, ring=True)
    plane = 4 * shape[1] * shape[2]
    return (sum(reps for *_, reps in passes),
            sum(reps * 2 * 2 * t * plane for _, t, _, _, reps in passes))


def modeled_sim_traffic(shape, n: int, fused: bool = True) -> float:
    """Modeled HBM bytes for ``n`` Gray-Scott steps under the schedule
    `multi_step_pallas` runs, for the bench harness's traffic model and
    the per-lever A/B accounting. ``fused=False`` (or any remainder no
    fused tile covers) charges the roll formulation's floor: one read +
    one write of u and v per step."""
    d, h, w = shape
    vol_bytes = 2 * 4.0 * d * h * w          # u + v, f32
    passes, remaining = schedule(shape, n) if fused else ((), n)
    total = remaining * 2.0 * vol_bytes
    for _, t, tz, th, reps in passes:
        total += reps * (_read_amp(tz, th, t) + 1.0) * vol_bytes
    return total
