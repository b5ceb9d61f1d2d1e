"""Device mesh construction and halo exchange.

This replaces the reference's rank/commSize bookkeeping received from MPI
through JNI (reference DistributedVolumes.kt:103-117): here the "communicator"
is a ``jax.sharding.Mesh`` and collectives are XLA ops over ICI/DCN, not
NCCL/MPI calls.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from scenery_insitu_tpu.config import MeshConfig

DEFAULT_AXIS = "ranks"


def make_mesh(num_devices: int = 0, axis_name: str = DEFAULT_AXIS,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """1D mesh over the compositing axis (≅ MPI COMM_WORLD of render ranks).
    num_devices == 0 → all local devices."""
    devs = list(devices) if devices is not None else jax.devices()
    if num_devices:
        if num_devices > len(devs):
            raise ValueError(
                f"requested {num_devices} devices, have {len(devs)}")
        devs = devs[:num_devices]
    import numpy as np
    return Mesh(np.array(devs), (axis_name,))


def from_config(cfg: MeshConfig) -> Mesh:
    return make_mesh(cfg.num_devices, cfg.axis_name)


def volume_sharding(mesh: Mesh, axis_name: str = DEFAULT_AXIS) -> NamedSharding:
    """Shard a global volume f32[D, H, W] along z (domain decomposition;
    ≅ OpenFPM splitting the grid across ranks)."""
    return NamedSharding(mesh, P(axis_name, None, None))


def halo_exchange_z(local: jnp.ndarray, axis_name: str = DEFAULT_AXIS,
                    h: int = 1) -> jnp.ndarray:
    """Pad a z-sharded block f32[Dn, H, W] with ``h`` neighbor slices on
    each side via ``ppermute`` over ICI → f32[Dn+2h, H, W].

    Edge ranks receive clamped copies of their own boundary slice,
    matching the single-device CLAMP_TO_EDGE sampling exactly — so
    distributed trilinear interpolation (h=1) AND radius-deep
    neighborhood operators like the AO box blur (h=radius+1) are
    seam-exact vs a single-device render (the reference's per-rank Volume
    nodes cannot interpolate across rank boundaries at all). ``h`` may
    not exceed the slab depth — deeper halos would need multi-hop
    exchanges; use fewer ranks or a smaller radius instead.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    dn = local.shape[0]
    if h > dn:
        raise ValueError(
            f"halo depth {h} exceeds the {dn}-slice slab — a neighbor "
            "holds fewer slices than the halo needs (shrink ao_radius or "
            "use fewer ranks / a deeper slab; planned render bands go "
            "through reslab_z, whose floor is min(plan), not D//n)")
    clamp_bot = jnp.repeat(local[:1], h, axis=0)
    clamp_top = jnp.repeat(local[-1:], h, axis=0)
    if n == 1:
        return jnp.concatenate([clamp_bot, local, clamp_top], axis=0)
    up = [(i, (i + 1) % n) for i in range(n)]
    down = [(i, (i - 1) % n) for i in range(n)]
    from_below = jax.lax.ppermute(local[-h:], axis_name, up)   # r-1's last h
    from_above = jax.lax.ppermute(local[:h], axis_name, down)  # r+1's first h
    bottom = jnp.where(idx == 0, clamp_bot, from_below)
    top = jnp.where(idx == n - 1, clamp_top, from_above)
    return jnp.concatenate([bottom, local, top], axis=0)


def validate_plan(plan, n: int, h: int = 1,
                  knob: str = "composite.rebalance_min_depth") -> tuple:
    """Static validation of a render z-plan (one band depth per rank).

    The min-slab constraint of a planned decomposition is ``min(plan)``,
    not ``D // n``: the shallowest band must still hold the deepest halo
    any consumer needs (1 slice for seam-exact trilinear; ``ao_radius +
    1`` for AO pre-shading). The diagnostic names the offending rank and
    the knob that fixes it."""
    plan = tuple(int(p) for p in plan)
    if len(plan) != n:
        raise ValueError(f"render plan has {len(plan)} bands for {n} "
                         f"ranks")
    if min(plan) < max(h, 1):
        r = min(range(n), key=lambda i: plan[i])
        raise ValueError(
            f"render plan band of rank {r} is {plan[r]} slice(s) deep — "
            f"below the {h}-slice halo this step needs (min-slab "
            f"constraint is min(plan), not D//n; raise {knob} to >= {h} "
            f"or use fewer ranks)")
    return plan


def _reslab_rows(local: jnp.ndarray, g_all, live_all,
                 axis_name: str = DEFAULT_AXIS) -> jnp.ndarray:
    """Materialize per-rank row sets from even z-shards — the shared
    core of `reslab_z` (contiguous bands) and `reslab_bricks`
    (arbitrary brick sets).

    ``g_all`` i32[n, R]: each rank's clamped GLOBAL source row per
    output row; ``live_all`` bool[n, R]: rows to fill (dead rows stay
    zero). Both are static numpy — the ladder is build-time geometry.
    Mechanism: one ``ppermute`` rotation per distinct (source − dest)
    shard offset any live row needs; each received even shard
    contributes its rows via a masked row gather. Near-even plans need
    2-3 hops; an adversarial brick map can need up to n-1 (correctness
    first — the steal planner's move cap keeps production maps local)."""
    import numpy as np

    n = jax.lax.axis_size(axis_name)
    dn = local.shape[0]
    g_all = np.asarray(g_all, np.int64)
    live_all = np.asarray(live_all, bool)
    offsets = sorted({int(o) for r in range(n)
                      for o in np.unique(g_all[r][live_all[r]] // dn) - r
                      } or {0})

    ri = jax.lax.axis_index(axis_name)
    g = jnp.asarray(g_all, jnp.int32)[ri]                 # [R]
    live = jnp.asarray(live_all)[ri]                      # [R]
    src = g // dn                                         # absolute source
    loc = g - src * dn                                    # row within shard
    bshape = (g_all.shape[1],) + (1,) * (local.ndim - 1)
    out = jnp.zeros((g_all.shape[1],) + local.shape[1:], local.dtype)
    for o in offsets:
        if o == 0:
            recv = local
        else:
            perm = [(i, (i - o) % n) for i in range(n)]
            recv = jax.lax.ppermute(local, axis_name, perm)
        sel = (src == ri + o) & live
        out = jnp.where(sel.reshape(bshape), jnp.take(recv, loc, axis=0),
                        out)
    return out


def reslab_z(local: jnp.ndarray, plan, axis_name: str = DEFAULT_AXIS,
             h: int = 1) -> jnp.ndarray:
    """Materialize this rank's PLANNED render band from the even z-slab
    shards (docs/PERF.md "Render rebalancing"): the sim sharding stays
    the even ``[Dn, H, W]`` split, and each rank assembles the contiguous
    global band ``[start_r - h, start_r + plan[r] + h)`` where ``start_r
    = sum(plan[:r])`` — with exactly `halo_exchange_z`'s boundary
    contract (edge halos are clamped copies of the global boundary
    slice, so distributed interpolation stays seam-exact vs a
    single-device render).

    shard_map needs one static shape per program, so every rank's band
    pads to ``max(plan) + 2h`` rows; rows past a rank's own ``plan[r] +
    2h`` are ZERO (the march masks them by its ownership bounds, and the
    occupancy pyramid admits zero for padded chunks, so skipping eats
    the padding).

    Mechanism: one ``ppermute`` rotation per distinct (source − dest)
    rank offset any band needs — near-even plans (the hysteresis/quantum
    regime) need 2-3 hops, like the halo exchange; each received even
    shard contributes its overlapping rows via a masked row gather. An
    even plan reproduces ``halo_exchange_z(local, h=h)`` exactly
    (row-for-row; tests assert equality)."""
    import numpy as np

    n = jax.lax.axis_size(axis_name)
    plan = validate_plan(plan, n, h=h)
    dn = local.shape[0]
    d = dn * n
    if sum(plan) != d:
        raise ValueError(f"render plan {plan} covers {sum(plan)} slices "
                         f"but the volume has {d}")
    starts = np.concatenate([[0], np.cumsum(plan)])[:n]
    out_depth = max(plan) + 2 * h
    # clamped global row ladder of every dest rank's output buffer
    lo = starts - h                                       # may be negative
    g_all = np.clip(lo[:, None] + np.arange(out_depth)[None, :], 0, d - 1)
    live_all = (np.arange(out_depth)[None, :]
                < (np.asarray(plan)[:, None] + 2 * h))    # trailing pad dead
    return _reslab_rows(local, g_all, live_all, axis_name)


def reslab_bricks(local: jnp.ndarray, bmap, axis_name: str = DEFAULT_AXIS,
                  h: int = 1) -> jnp.ndarray:
    """Materialize this rank's BRICK SET from the even z-slab shards
    (docs/SCENARIOS.md "Brick maps"): ``bmap`` is a
    `parallel.bricks.BrickMap`; each of the rank's ``bmap.slots`` slots
    holds one brick's global rows ``[start - h, start + bz + h)`` with
    exactly `halo_exchange_z`'s boundary contract (rows clamp only at
    the GLOBAL edges; interior brick faces receive their true
    neighbors, whichever rank owns them — what keeps per-brick
    interpolation seam-exact under any ownership). Absent slots (a rank
    owning fewer bricks than the busiest) come back all-zero.

    Returns ``[slots, bz + 2h, H, W]`` — `_reslab_rows` does the
    ppermute routing on the flattened ladder."""
    import numpy as np

    n = jax.lax.axis_size(axis_name)
    if bmap.n_ranks != n:
        raise ValueError(f"brick map built for {bmap.n_ranks} ranks on a "
                         f"{n}-rank mesh")
    dn = local.shape[0]
    d = dn * n
    if bmap.depth != d:
        raise ValueError(f"brick map covers depth {bmap.depth} but the "
                         f"volume has {d} slices")
    bz = bmap.brick_depth
    rows = bz + 2 * h
    table = bmap.start_table()                            # [n, B]
    ladder = np.arange(rows)[None, None, :] - h
    g_all = np.clip(table[:, :, None] + ladder, 0, d - 1)
    live_all = np.broadcast_to((table >= 0)[:, :, None], g_all.shape)
    out = _reslab_rows(local, g_all.reshape(n, -1),
                       live_all.reshape(n, -1), axis_name)
    return out.reshape((bmap.slots, rows) + local.shape[1:])


def reslab_bricks_lod(local: jnp.ndarray, bmap,
                      axis_name: str = DEFAULT_AXIS, h: int = 1):
    """Materialize this rank's MULTI-RESOLUTION brick set from the even
    z-slab shards (docs/PERF.md "LOD marching"): the level-aware twin of
    `reslab_bricks`. Returns ``{level: [slots_at(level), bz/f + 2h,
    H/f, W/f]}`` for every level present in the map (f = 2^level) —
    downsampling happens HERE, on device, after the ppermute routing of
    the FINE rows, so HBM holds fine data only for level-0 bricks.

    Per level, each slot gathers the fine global rows ``[start - h*f,
    start + bz + h*f)`` (the halo deepens with the level so the pooled
    copy still carries ``h`` COARSE halo rows, with exactly
    `halo_exchange_z`'s boundary contract at the global edges) and
    average-pools by ``f`` in all three dims — f32 accumulation, cast
    back to the input dtype, so a bf16 render copy pools without
    compounding rounding. A coarse voxel tiles ``f^3`` fine voxels
    exactly: the pooled volume keeps the band's corner origin with
    ``spacing * f`` (the corner-origin convention makes the pooled
    centers land where trilinear expects them — no half-voxel shift).

    The brick depth divides by ``f`` by BrickMap construction; the
    in-plane extents must too — a clear error here, not a silent
    mis-shape. Level 0 reproduces `reslab_bricks`' rows bit-for-bit
    (same ladder, same routing, no pooling)."""
    import numpy as np

    n = jax.lax.axis_size(axis_name)
    if bmap.n_ranks != n:
        raise ValueError(f"brick map built for {bmap.n_ranks} ranks on a "
                         f"{n}-rank mesh")
    dn = local.shape[0]
    d = dn * n
    if bmap.depth != d:
        raise ValueError(f"brick map covers depth {bmap.depth} but the "
                         f"volume has {d} slices")
    bz = bmap.brick_depth
    hh, ww = local.shape[1], local.shape[2]
    out = {}
    for lvl in bmap.levels_present():
        f = 1 << lvl
        if hh % f or ww % f:
            raise ValueError(
                f"brick level {lvl} pools by {f} but the in-plane "
                f"extents ({hh}, {ww}) do not divide — cap "
                f"lod.max_level so 2^level tiles every axis")
        rows_f = bz + 2 * h * f
        table = bmap.start_table_at(lvl)                  # [n, B_l]
        slots = table.shape[1]
        ladder = np.arange(rows_f)[None, None, :] - h * f
        g_all = np.clip(table[:, :, None] + ladder, 0, d - 1)
        live_all = np.broadcast_to((table >= 0)[:, :, None], g_all.shape)
        fine = _reslab_rows(local, g_all.reshape(n, -1),
                            live_all.reshape(n, -1), axis_name)
        fine = fine.reshape((slots, rows_f) + local.shape[1:])
        if f == 1:
            out[lvl] = fine
            continue
        x = fine.reshape(slots, rows_f // f, f, hh // f, f, ww // f, f)
        x = jnp.mean(x.astype(jnp.float32), axis=(2, 4, 6))
        out[lvl] = x.astype(local.dtype)
    return out
