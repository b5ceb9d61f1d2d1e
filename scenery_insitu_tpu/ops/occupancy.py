"""Frame-coherent occupancy pyramid — the empty-space acceleration
structure of the MXU slice march (≅ the reference's OctreeCells grid,
VDIGenerator.comp:232-254 + GridCellsToZero.comp, which it rebuilds by
atomic-add during every generation pass; here the structure is VALUE
RANGES, built once per frame and shared).

Three ideas, layered:

1. **One structure per frame, not one reduction per march.** The legacy
   path (`slicer.occupancy_for`) re-ran `permute_volume` plus a
   full-volume reduction at every call site — the counting march, the
   writing march, the temporal seeder and the plain render each paid an
   extra HBM sweep. `pyramid_from_volume` computes the two-level pyramid
   (per-chunk and per-(chunk × v-tile) value ranges, with the one-row
   apron argument of `slicer.chunk_occupancy_vtiles`) ONCE, on a permuted
   volume it can share with the march itself, and every consumer reads
   the same arrays.

2. **Ranges, not booleans.** The pyramid stores per-cell [lo, hi] value
   ranges of the field; occupancy gates are derived by pushing the range
   through the transfer function's conservative bound
   (`tf.max_alpha_in`). Ranges are TF-independent, so the same pyramid
   serves any number of marches, transfer functions, and the load
   histogram — and they can come from somewhere cheaper than a volume
   sweep:

3. **Sim-fused updates.** The time-fused Gray-Scott stencil
   (sim/pallas_stencil.py) already touches every voxel of the field each
   step; its optional ranges epilogue emits per-(z, y)-brick min/max of
   the rendered field as (1, 1) SMEM reductions riding the same kernel —
   near-free. `pyramid_from_ranges` maps those DATA-layout brick ranges
   onto the MARCH-layout (chunk × v-tile) cells of any `AxisSpec`
   conservatively (outward-rounded brick intervals, apron rows included,
   a bf16 widening when the march reads a bf16 copy), so a frame can skip empty space without ever re-reading
   the volume. When the Pallas path degrades, `field_ranges` is the lax
   fallback reduction (one sweep of the field in data layout — still
   cheaper than permute + reduce, and routed through ``obs.degrade``).

The same per-rank pyramid also drives the sort-last fold: its live
fraction is the per-rank load histogram behind
``CompositeConfig.k_budget = "occupancy"`` (`k_budget_target`), which
re-targets the adaptive supersegment count so sparse slabs stop chasing
the same K as the densest rank (docs/PERF.md "Empty-space skipping").

Conservativeness contract (property-tested in tests/test_occupancy.py):
a cell the pyramid gates off is PROVABLY zero-alpha — in-plane bilinear
resampling keeps values inside each covered row-pair's range (the apron
makes every adjacent-row pair fully contained in at least one band), and
`max_alpha_in` bounds any transfer function, band-pass included, over
the whole interval.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax.numpy as jnp

from scenery_insitu_tpu import obs

# one storage rounding of a bf16 march copy moves a value by at most
# 2^-8 relative (8 mantissa bits); ranges built from the f32 sim field
# widen by this before gating a bf16 march (pyramid_from_ranges)
_BF16_EPS = 2.0 ** -8


class FieldRanges(NamedTuple):
    """Per-brick value ranges of a scalar field in DATA layout
    ``[D, H, W]``: brick (i, j) covers ``z ∈ [i*bz, (i+1)*bz) ×
    y ∈ [j*by, (j+1)*by) × all x`` where ``bz = D // lo.shape[0]`` and
    ``by = H // lo.shape[1]`` (brick sizes are derived from shapes — the
    arrays ARE the structure, so they ride jit boundaries and scan
    carries as plain pytrees)."""

    lo: jnp.ndarray   # f32[nzb, nyb]
    hi: jnp.ndarray   # f32[nzb, nyb]


def default_bricks(shape: Tuple[int, int, int]) -> Tuple[int, int]:
    """Canonical (nzb, nyb) brick grid for a field shape: ~32 z bricks ×
    ~OCCUPANCY_VTILES_DEFAULT y bricks, snapped down to divisors so
    reshaping reductions stay exact. Matches the flagship march geometry
    (chunk=16 slices at 512^3 → bz=16 aligns with chunks; the y-brick
    cap tracks the benched vtile count)."""
    from scenery_insitu_tpu.config import OCCUPANCY_VTILES_DEFAULT

    d, h, _ = shape
    return _cap_divisor(d, 32), _cap_divisor(h, OCCUPANCY_VTILES_DEFAULT)


def _cap_divisor(n: int, cap: int) -> int:
    b = min(n, cap)
    while n % b:
        b -= 1
    return b


def field_ranges(field: jnp.ndarray, nzb: int, nyb: int) -> FieldRanges:
    """Lax fallback reduction: per-brick min/max of ``field [D, H, W]``
    in one sweep of the data layout (no permute). Requires ``nzb | D``
    and ``nyb | H``; x is fully reduced (the lane axis the fused-stencil
    epilogue cannot split either)."""
    d, h, w = field.shape
    if d % nzb or h % nyb:
        raise ValueError(f"brick grid ({nzb}, {nyb}) does not divide "
                         f"field shape {field.shape}")
    x = field.reshape(nzb, d // nzb, nyb, h // nyb, w).astype(jnp.float32)
    return FieldRanges(lo=jnp.min(x, axis=(1, 3, 4)),
                       hi=jnp.max(x, axis=(1, 3, 4)))


def remap_ranges(lo: jnp.ndarray, hi: jnp.ndarray,
                 to_shape: Tuple[int, int]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Re-grid brick ranges to another brick count per axis,
    conservatively: reducing (from % to == 0) is exact, refining
    (to % from == 0) repeats the coarse range over its fine bricks, and
    incommensurate grids reduce to their gcd granularity first (e.g. a
    6-brick kernel grid onto a 32-brick canonical grid keeps 2 bands
    instead of collapsing to one global range) — a REAL coarsening
    either way, so it lands on the fallback ledger. Used to normalize
    the fused-stencil epilogue's native (tz, th) granularity onto the
    caller's canonical grid so shapes stay fixed across the greedy
    multi-T decomposition."""
    import math

    def one_axis(x, n_to, axis, red):
        n_from = x.shape[axis]
        if n_from == n_to:
            return x
        if n_from % n_to == 0:
            r = n_from // n_to
            shp = x.shape[:axis] + (n_to, r) + x.shape[axis + 1:]
            return red(x.reshape(shp), axis=axis + 1)
        if n_to % n_from != 0:
            # incommensurate: coarsen to the gcd granularity (>= 1),
            # then refine — structure survives at g bands instead of
            # one global range. Static condition -> trace-time ledger.
            g = math.gcd(n_from, n_to)
            obs.degrade("occupancy.ranges_remap", f"{n_from} bricks",
                        f"{g} bands",
                        f"kernel brick grid {n_from} incommensurate "
                        f"with canonical {n_to} on axis {axis} — "
                        f"occupancy resolution coarsens", warn=False)
            shp = x.shape[:axis] + (g, n_from // g) + x.shape[axis + 1:]
            x = red(x.reshape(shp), axis=axis + 1)
            n_from = g
        return jnp.repeat(x, n_to // n_from, axis=axis)

    for axis in (0, 1):
        lo = one_axis(lo, to_shape[axis], axis, jnp.min)
        hi = one_axis(hi, to_shape[axis], axis, jnp.max)
    return lo, hi


# ----------------------------------------------------------- the pyramid


class OccupancyPyramid(NamedTuple):
    """Two-level march-layout occupancy for one (volume, AxisSpec) pair.

    Level 0: per-(chunk × v-tile) cell value ranges ``lo/hi
    f32[nchunks, nt]`` (pre-shaded RGBA volumes store ALPHA ranges) and
    the derived gate ``tiles bool[nchunks, nt]``. Level 1: the per-chunk
    gate ``chunks bool[nchunks]`` derived from the union of the cell
    ranges (aprons only widen within a chunk, so it equals the
    whole-slab reduction exactly). ``nt == 1`` when the spec does no
    in-plane tiling."""

    lo: jnp.ndarray       # f32[nchunks, nt]
    hi: jnp.ndarray       # f32[nchunks, nt]
    chunks: jnp.ndarray   # bool[nchunks]
    tiles: jnp.ndarray    # bool[nchunks, nt]

    def gate(self, spec):
        """The structure `slicer.slice_march` consumes for ``spec``:
        None when skipping is off, bool[nchunks] for chunk-only
        skipping, (chunks, tiles) when the spec tiles in-plane — the
        same contract `slicer.occupancy_for` always had."""
        if not spec.skip_empty:
            return None
        if spec.vtiles > 0:
            return self.chunks, self.tiles
        return self.chunks

    def live_fraction(self) -> jnp.ndarray:
        """f32[] fraction of level-0 cells that can contribute opacity —
        the per-rank load signal of the occupancy K budget and the bench
        artifact's headline sparsity number."""
        return jnp.mean(self.tiles.astype(jnp.float32))

    def chunk_live_fractions(self) -> jnp.ndarray:
        """f32[nchunks] per-chunk live-tile fraction (the histogram
        axis benchmark artifacts record)."""
        return jnp.mean(self.tiles.astype(jnp.float32), axis=1)


def resolved_tiles(spec, nv: int) -> int:
    """The tile count a march over a volume with ``nv`` v-rows actually
    uses: ``spec.vtiles`` re-clamped so every band keeps >= 2 rows
    (distributed slabs can be far smaller than the global shape
    `make_spec` clamped against). A clamp that REDUCES the configured
    count is recorded on the fallback ledger — it silently coarsens the
    skip granularity (ISSUE 6 satellite; the old path said nothing)."""
    if spec.vtiles <= 0:
        return 1
    nt = max(1, min(spec.vtiles, nv // 2))
    if nt < spec.vtiles:
        obs.degrade("occupancy.vtiles_clamp", str(spec.vtiles), str(nt),
                    f"v extent {nv} supports at most {max(1, nv // 2)} "
                    f"bands of >= 2 rows (tiny distributed slab?)",
                    warn=False)
    return nt


def _tile_bands(nv: int, nt: int):
    """Row intervals [r0, r1) of the nt v-tiles INCLUDING the one-row
    apron (see slicer.chunk_occupancy_vtiles: an output row's bilinear
    support may straddle a band boundary; the apron makes every
    adjacent-row pair fully contained in at least one band). The last
    band absorbs the remainder."""
    tv = nv // nt
    return [(max(t * tv - 1, 0),
             nv if t == nt - 1 else min((t + 1) * tv + 1, nv))
            for t in range(nt)]


def _gates(tf, lo, hi, pre_shaded: bool, alpha_eps: float):
    """(chunks, tiles) gates from cell ranges. Scalar volumes push the
    range through the TF's conservative alpha bound; pre-shaded volumes
    gate on the stored alpha directly."""
    if pre_shaded:
        tiles = hi > alpha_eps
        return jnp.any(tiles, axis=1), tiles
    cl = lambda x: jnp.clip(x, 0.0, 1.0)
    tiles = tf.max_alpha_in(cl(lo), cl(hi)) > alpha_eps
    chunks = tf.max_alpha_in(cl(jnp.min(lo, axis=1)),
                             cl(jnp.max(hi, axis=1))) > alpha_eps
    return chunks, tiles


def volume_ranges(vol, spec, volp: Optional[jnp.ndarray] = None,
                  ntiles: Optional[int] = None):
    """(lo, hi) f32[nchunks, nt]: the exact per-(chunk x v-tile) value
    ranges of the march layout, in MARCH order and normalized — ONE pass
    over the layout, which holds everything of the pyramid that depends
    on the field and the spec alone (the gates also take the TF:
    `pyramid_from_volume`). ``volp`` (the `slicer.permute_volume`
    output, storage order) lets the caller share the frame's layout;
    chunk boundaries are `slicer.march_chunks`' (full chunks counted
    from the march's front, the remainder last, no pad), so the pyramid
    and the march can never disagree on slab layout. A field that never
    changes has them computed once (`parallel/pipeline
    .distributed_volume_ranges_mxu`)."""
    from scenery_insitu_tpu.core.volume import value_scale
    from scenery_insitu_tpu.ops import slicer

    if volp is None:
        volp = slicer.permute_volume(vol, spec)
    if vol.data.ndim == 4:
        volp = volp[:, 3]                                  # alpha plane
    c = spec.chunk
    s_total, nv = volp.shape[:2]
    nfull, rem = divmod(s_total, c)
    fwd = spec.sign > 0
    # storage slices of the full chunks and of the remainder
    full = volp[:nfull * c] if fwd else volp[rem:]
    rest = volp[nfull * c:] if fwd else volp[:rem]
    nt = resolved_tiles(spec, nv) if ntiles is None else max(1, ntiles)

    def cells(x, n):
        # reduce in storage dtype (bf16 march copies, raw integers)
        out = [[red(x[:, r0:r1].reshape(n, -1), axis=1)
                for r0, r1 in _tile_bands(nv, nt)]
               for red in (jnp.min, jnp.max)]
        return [jnp.stack(o, axis=1).astype(jnp.float32) for o in out]

    parts = []
    if nfull:
        parts.append(cells(full, nfull) if fwd
                     else [x[::-1] for x in cells(full, nfull)])
    if rem:
        parts.append(cells(rest, 1))
    lo, hi = (jnp.concatenate(x) for x in zip(*parts))
    scale = value_scale(volp.dtype)
    if scale != 1.0:
        lo, hi = lo * jnp.float32(scale), hi * jnp.float32(scale)
    return lo, hi


def pyramid_from_volume(vol, tf, spec, volp: Optional[jnp.ndarray] = None,
                        alpha_eps: float = 1e-5,
                        ntiles: Optional[int] = None,
                        ranges=None) -> OccupancyPyramid:
    """Build the pyramid from the volume itself — exact ranges
    (`volume_ranges`: one pass over the march layout, or ``ranges``,
    the same two arrays computed before for a field that has not
    changed since) pushed through the TF's conservative alpha bound.

    ``ntiles`` overrides the spec-derived tile count (used by the legacy
    `slicer.chunk_occupancy` wrapper, which is the nt=1 level alone)."""
    rec = obs.get_recorder()
    kept = ranges is not None
    if not kept:
        ranges = volume_ranges(vol, spec, volp, ntiles)
        rec.count("occupancy_pyramid_builds")
    lo, hi = ranges
    chunks, tiles = _gates(tf, lo, hi, vol.data.ndim == 4, alpha_eps)
    rec.event("occupancy_build", source="kept_ranges" if kept else "volume",
              nchunks=int(lo.shape[0]), ntiles=int(lo.shape[1]))
    return OccupancyPyramid(lo, hi, chunks, tiles)


def pyramid_from_ranges(ranges: FieldRanges, vol, tf, spec,
                        alpha_eps: float = 1e-5) -> OccupancyPyramid:
    """Build the pyramid from sim-provided DATA-layout brick ranges —
    zero volume traffic. ``ranges`` must describe exactly the field the
    volume wraps (``vol.data`` shape ``[D, H, W]``, scalar; the
    distributed slab path with its halo rows keeps `pyramid_from_volume`
    instead).

    Conservative by construction: each (chunk × v-tile) cell takes the
    union range of every brick its region (apron rows included) can
    touch, with brick intervals rounded outward; a bf16 march copy (``spec.render_dtype``) additionally
    widens the range by one storage rounding. Cells this pyramid gates
    off are a SUBSET of what `pyramid_from_volume` gates off — coarser
    skipping, identical output (the march's skip path is exact)."""
    if vol.data.ndim == 4:
        raise ValueError("sim field ranges describe a scalar field; "
                         "pre-shaded RGBA volumes build from the volume")
    d, h, w = vol.data.shape
    nzb, nyb = ranges.lo.shape
    if d % nzb or h % nyb:
        raise ValueError(f"brick grid {ranges.lo.shape} does not divide "
                         f"volume shape {vol.data.shape}")
    bz, by = d // nzb, h // nyb
    a = spec.axis

    # orient the brick grid as [slice-axis bricks, v-axis bricks]
    if a == 2:            # march z, v = y
        sl_lo, sl_hi = ranges.lo, ranges.hi
        sb, s_total, vb = bz, d, by
    elif a == 1:          # march y, v = z
        sl_lo, sl_hi = ranges.lo.T, ranges.hi.T
        sb, s_total, vb = by, h, bz
    else:                 # march x: bricks don't resolve x — one global
        #                   slice brick; in-plane tiles still resolve z
        sl_lo = jnp.min(ranges.lo, axis=1)[None, :]        # [1, nzb]
        sl_hi = jnp.max(ranges.hi, axis=1)[None, :]
        sb, s_total, vb = w, w, bz

    c = spec.chunk
    nchunks = -(-s_total // c)
    nv = vol.data.shape[_data_dim(spec.v_axis)]
    nt = resolved_tiles(spec, nv)

    # per-tile band ranges along the v bricks (apron rows included)
    band_lo, band_hi = [], []
    for r0, r1 in _tile_bands(nv, nt):
        b0, b1 = r0 // vb, -(-r1 // vb)
        band_lo.append(jnp.min(sl_lo[:, b0:b1], axis=1))
        band_hi.append(jnp.max(sl_hi[:, b0:b1], axis=1))
    band_lo = jnp.stack(band_lo, axis=1)                   # [nsb, nt]
    band_hi = jnp.stack(band_hi, axis=1)

    # per-chunk reduction along the slice-axis bricks: marched slice
    # interval -> data interval (sign flip) -> outward brick interval
    los, his = [], []
    for ci in range(nchunks):
        m0, m1 = ci * c, min((ci + 1) * c, s_total)
        d0, d1 = (m0, m1) if spec.sign > 0 else (s_total - m1, s_total - m0)
        b0, b1 = d0 // sb, -(-d1 // sb)
        lo_c = jnp.min(band_lo[b0:b1], axis=0)
        hi_c = jnp.max(band_hi[b0:b1], axis=0)
        los.append(lo_c)
        his.append(hi_c)
    lo = jnp.stack(los)                                    # [nchunks, nt]
    hi = jnp.stack(his)
    if spec.render_dtype == "bf16":
        # the march reads a bf16 COPY of the f32 field these ranges
        # describe — one storage rounding can push a voxel past the f32
        # extremum, so widen before gating
        lo = lo - jnp.abs(lo) * _BF16_EPS
        hi = hi + jnp.abs(hi) * _BF16_EPS
    chunks, tiles = _gates(tf, lo, hi, False, alpha_eps)
    rec = obs.get_recorder()
    rec.count("occupancy_ranges_builds")
    rec.event("occupancy_build", source="sim_ranges",
              nchunks=int(nchunks), ntiles=int(nt))
    return OccupancyPyramid(lo, hi, chunks, tiles)


def _data_dim(axis_xyz: int) -> int:
    # xyz axis index -> Volume.data dim counted from the end (mirrors
    # slicer._DATA_DIM without importing the module at call time)
    return {0: -1, 1: -2, 2: -3}[axis_xyz]


# ------------------------------------------------------ load-aware K budget


def k_budget_target(live_frac, total_live, n_ranks: int, k: int,
                    k_min: int = 4) -> jnp.ndarray:
    """f32[] per-rank adaptive segment-count target under
    ``CompositeConfig.k_budget = "occupancy"``: this rank's share of the
    mesh-wide budget ``n_ranks * k``, proportional to its occupancy-
    pyramid live fraction, clamped to ``[k_min, k]``.

    Array SHAPES stay at K on every rank (one SPMD program), so this is
    a quality/work re-balance, not a memory one: the adaptive threshold
    controller closes ~k_r segments on rank r instead of chasing K
    everywhere — sparse slabs emit coarser VDIs (their content cannot
    fill K slots anyway; slots they don't start stay +inf and cost the
    exchange nothing after qpack8), while dense slabs keep full fidelity
    and stop being the only rank whose march runs at the knife edge of
    the shared threshold band (docs/PERF.md "Empty-space skipping").
    An all-empty mesh (total ~ 0) degenerates to the static budget."""
    live_frac = jnp.asarray(live_frac, jnp.float32)
    total = jnp.maximum(jnp.asarray(total_live, jnp.float32), 1e-12)
    share = n_ranks * k * live_frac / total
    share = jnp.where(total > 1e-9, share, jnp.float32(k))
    return jnp.clip(share, jnp.float32(min(k_min, k)), jnp.float32(k))


# -------------------------------------------- uneven z-slab render plans


# Work model of one z slice (docs/PERF.md "Render rebalancing"): a live
# slice costs 1 + base, an empty one only base — skipping makes air
# cheap, not free (the chunk scan still iterates, the pyramid gate still
# evaluates, padded fold chunks still close segments). The committed CPU
# A/B (benchmarks/results/rebalance_ab_r10_cpu.json) is the measured
# anchor for the modeled straggler factors derived from this.
PLAN_BASE_COST = 0.05


def z_live_profile(field: jnp.ndarray, tf, nzb: int = 0, nyb: int = 0,
                   alpha_eps: float = 1e-5) -> jnp.ndarray:
    """f32[nzb] per-z-brick live fraction of a scalar field ``[D, H, W]``
    — the host-side re-plan signal of ``CompositeConfig.rebalance ==
    "occupancy"``. One `field_ranges` sweep in data layout (no permute)
    gated through the TF's conservative alpha bound, reduced over the
    in-plane bricks: entry i is the fraction of (y-brick) cells in
    z band ``[i*D/nzb, (i+1)*D/nzb)`` that can contribute opacity.
    ``nzb``/``nyb`` default to `default_bricks`. In the distributed
    session each rank runs this on its EVEN slab and the profiles
    concatenate along the mesh axis into the global z profile
    `slice_plan` consumes."""
    d_nzb, d_nyb = default_bricks(field.shape)
    nzb = nzb or d_nzb
    nyb = nyb or d_nyb
    fr = field_ranges(field, nzb, nyb)
    cl = lambda x: jnp.clip(x, 0.0, 1.0)
    live = tf.max_alpha_in(cl(fr.lo), cl(fr.hi)) > alpha_eps
    return jnp.mean(live.astype(jnp.float32), axis=1)


def z_range_profile(field: jnp.ndarray, nzb: int = 0):
    """(lo f32[nzb], hi f32[nzb]) per-z-brick sampled value range of a
    scalar field ``[D, H, W]``, clipped to the TF's [0, 1] domain — the
    host-side signal of the LOD planner's TF-straddle coarsening gate
    (`parallel.lod.select_levels`; docs/PERF.md "LOD marching"): a brick
    whose range crosses an opacity edge must keep level 0, and the
    decision needs the range itself, not the live reduction
    `z_live_profile` collapses it to. One `field_ranges` sweep with a
    single in-plane brick (the gate is per z-brick). In the distributed
    session each rank profiles its EVEN slab and the ranges concatenate
    along the mesh axis."""
    nzb = nzb or default_bricks(field.shape)[0]
    fr = field_ranges(field, nzb, 1)
    return (jnp.clip(fr.lo[:, 0], 0.0, 1.0),
            jnp.clip(fr.hi[:, 0], 0.0, 1.0))


def _slice_work(live_profile, d: int, base_cost: float):
    """f64[d] per-slice march work from a per-z-bin live profile
    (``len(live_profile)`` must divide ``d``)."""
    import numpy as np

    prof = np.asarray(live_profile, np.float64).clip(0.0, None)
    nb = prof.shape[0]
    if nb == 0 or d % nb:
        raise ValueError(f"live profile has {nb} bins which do not "
                         f"divide depth {d}")
    return np.repeat(prof, d // nb) + base_cost


def slice_plan(live_profile, d: int, n: int, min_depth: int = 1,
               quantum: int = 1, prev=None, hysteresis: float = 0.0,
               base_cost: float = PLAN_BASE_COST,
               max_depth: int = 0):
    """Per-rank contiguous z-slice counts equalizing live march work
    (docs/PERF.md "Render rebalancing") — host-side, numpy, static.

    ``live_profile`` (f32[nb], nb | d) is the global per-z-bin live
    fraction (`z_live_profile`, rank profiles concatenated). Greedy
    prefix-sum equalization places band boundary r at the slice where
    cumulative work first reaches r/n of the total, snapped to the
    nearest ``quantum`` multiple and clamped so every band keeps
    ``min_depth`` slices. Conservation is structural: boundaries are a
    monotone ladder from 0 to d, so ``sum(plan) == d`` always.

    ``max_depth`` caps any band's depth (0 = the default cap,
    ``2 * ceil(d / n)``): shard_map pads every rank's band to
    ``max(plan)``, so an unbounded plan — one rank owning a huge empty
    region — would make EVERY rank scan (and skip) that many chunks;
    the cap bounds the padding tax at the cost of splitting large empty
    regions across several ranks (air is cheap to share).

    ``prev``/``hysteresis`` stabilize the plan across frames: when every
    boundary of the fresh plan is within ``hysteresis * (d / n)`` slices
    of ``prev``'s, ``prev`` is returned UNCHANGED (object-equal), so the
    caller can key recompiles on plan identity. Returns a tuple of n
    ints."""
    import numpy as np

    if n < 1:
        raise ValueError(f"need >= 1 rank, got {n}")
    min_depth = max(1, min(int(min_depth), d // n))
    quantum = max(1, int(quantum))
    max_depth = int(max_depth) or 2 * (-(-d // n))
    max_depth = max(max_depth, -(-d // n))          # keep n bands feasible
    w = _slice_work(live_profile, d, base_cost)
    cw = np.cumsum(w)
    total = float(cw[-1])
    bounds = [0]
    for r in range(1, n):
        target = total * r / n
        z = int(np.searchsorted(cw, target, side="left")) + 1
        z = int(round(z / quantum)) * quantum
        lo = max(bounds[-1] + min_depth, d - (n - r) * max_depth)
        hi = min(d - (n - r) * min_depth, bounds[-1] + max_depth)
        bounds.append(int(min(max(z, lo), hi)))
    bounds.append(d)
    plan = tuple(int(b1 - b0) for b0, b1 in zip(bounds, bounds[1:]))
    if prev is not None and len(prev) == n and hysteresis > 0.0:
        pb = np.concatenate([[0], np.cumsum(np.asarray(prev, np.int64))])
        if pb[-1] == d and np.max(np.abs(np.asarray(bounds) - pb)) \
                <= hysteresis * d / n:
            return tuple(int(p) for p in prev)
    return plan


def even_plan(d: int, n: int):
    """The identity render plan: the even z-slab split itself."""
    if d % n:
        raise ValueError(f"depth {d} not divisible by {n} ranks")
    return (d // n,) * n


def plan_work(live_profile, d: int, plan,
              base_cost: float = PLAN_BASE_COST):
    """Per-rank modeled march work of a render plan under the slice work
    model — the numerator of the straggler factor."""
    import numpy as np

    w = _slice_work(live_profile, d, base_cost)
    if sum(plan) != d:
        raise ValueError(f"plan {plan} does not cover depth {d}")
    bounds = np.concatenate([[0], np.cumsum(np.asarray(plan, np.int64))])
    return [float(w[b0:b1].sum()) for b0, b1 in zip(bounds, bounds[1:])]


def straggler_factor(live_profile, d: int, plan,
                     base_cost: float = PLAN_BASE_COST) -> float:
    """max/mean per-rank modeled march work — the frame-barrier term the
    rebalance attacks (frame time is the max over ranks; mean is the
    perfectly-balanced floor). 1.0 = no straggler."""
    import numpy as np

    work = plan_work(live_profile, d, plan, base_cost)
    return float(np.max(work) / max(np.mean(work), 1e-12))
