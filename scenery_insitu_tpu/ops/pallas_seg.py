"""Pallas TPU kernels of the march's folds: the segmented-scan write fold
(ops/seg_fold.py) under its two feeds, and the histogram march's count
kernel.

The write fold is the XLA reference's algorithm — parallel start flags,
segment ids by running count, segmented transmittance, K masked
reductions — with the memory movement pinned down: the sample chunk, the
K-slot state and the per-slice ``(slot, v)`` records all live in VMEM
pixel strips (sized by ops/pallas_util.py), and the ``[K,...]`` state
crosses HBM once per chunk via ``input_output_aliases``. Phase A carries
just four small values per pixel between slices (running start count,
running transmittance, prev rgb, prev empty) and writes each slice's
``(slot, premultiplied-scaled rgba)`` record straight to a VMEM scratch
ref, so no live range spans the loop; phase B re-reads the scratch per
slot row — VMEM-to-register traffic, not HBM.

Phase B's trip count follows the data. Per pixel the slots a chunk
touches are one interval; phase A keeps its two ends as planes, and per
8 x 128 tile of the strip phase B merges only the hull ``[lo, hi)`` of
the tile's intervals (`tile_slot_bounds`) and COPIES the rows outside
it: the state's outputs are aliased to its inputs in HBM but are their
own VMEM buffers, so an untouched row must still be written. A tile
with no live sample merges nothing. Every row is visited once, merged
or copied, so a dense tile costs what a plain ``fori_loop(0, K)`` does
plus two reductions, and the results are the same numbers (the copied
rows merged zeros before). The kernel counts the rows it merged and
visited per tile in a small SMEM output carried with the state
(`init_slot_counts`, `fold_slot_counts`).

One kernel body, two feeds, chosen per march by `slicer.fold_schedule`:
`fused_fold_chunk` (kernel ``sitpu_fold_fused``) takes the march's
one-channel VALUE plane and shades it in VMEM; `fold_chunk_packed`
(``sitpu_fold_seg_compact``) takes the shaded rgba chunk of a march that
has no scalar volume or no concrete transfer function. Both form the
depths in-kernel from the per-slice ratios and the per-pixel ray length.

Semantics are identical to ``seg_fold.seg_fold_chunk`` (tests pin
interpret-mode equality) and therefore to C sequential ``ss.push`` calls
up to fp association (≅ the reference's fused single-kernel generation,
VDIGenerator.comp:380-529 + AccumulateVDI.comp:69-98).

State layout (4 aliased arrays): ``color f32[K,4,H,W]``, ``depth
f32[K,2,H,W]`` (start/end; start init +inf, end init -inf), ``small
f32[5,H,W]`` = cnt[0] (f32-encoded), prev_rgb[1:4], prev_empty[4], and
the kernel's account ``slots i32[2, tiles]`` (rows merged, rows visited).
Helpers convert to/from ``seg_fold.SegFoldState`` so the march code
handles ONE state type. On CPU (tests, the virtual mesh) the kernels run
in interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from scenery_insitu_tpu.ops import seg_fold as sf
from scenery_insitu_tpu.ops import supersegments as ss
from scenery_insitu_tpu.ops.pallas_util import (TILE_H, TILE_W,
                                                pick_block_w,
                                                should_interpret, strip_fpp)

_CNT, _PREV_RGB, _PREV_EMPTY = 0, slice(1, 4), 4
_NSMALL = 5
# the slot-row account lives whole in SMEM: a few scalars a grid step
_SLOTS_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def init_seg_packed(k: int, height: int, width: int):
    """Packed fold state ≅ seg_fold.init_seg_state — built directly in
    packed layout so a march can carry the tuple through its scan with
    no per-chunk stack/concat traffic (the depth plane alone is
    [K,2,H,W]; re-materializing it every chunk would cost more HBM than
    the kernel's own state pass)."""
    color = jnp.zeros((k, 4, height, width), jnp.float32)
    depth = jnp.stack([
        jnp.full((k, height, width), jnp.inf, jnp.float32),
        jnp.full((k, height, width), -jnp.inf, jnp.float32)], axis=1)
    small = jnp.zeros((_NSMALL, height, width), jnp.float32)
    small = small.at[_PREV_EMPTY].set(1.0)
    return (color, depth, small, init_slot_counts(height, width))


def init_slot_counts(height: int, width: int):
    """The fold's own account, i32[2, tiles]: per 8 x 128 tile the slot
    rows its K-loops merged and the rows they visited, added up by the
    kernel over the chunks it ran on (`fold_slot_counts`)."""
    return jnp.zeros((2, slot_tiles(height, width)), jnp.int32)


def fold_slot_counts(packed) -> jnp.ndarray:
    """i32[2]: the slot rows the folds of this state merged, and the
    rows they visited (K x tiles x chunks the kernel ran on)."""
    return packed[3].sum(axis=1)


def pack_seg_state(st: sf.SegFoldState):
    small = jnp.concatenate([
        st.cnt.astype(jnp.float32)[None],
        st.prev_rgb,
        st.prev_empty.astype(jnp.float32)[None]])
    return (st.out_color,
            jnp.stack([st.out_start, st.out_end], axis=1),
            small, init_slot_counts(*st.cnt.shape))


def unpack_seg_state(packed) -> sf.SegFoldState:
    color, depth, small = packed[:3]
    return sf.SegFoldState(
        out_color=color, out_start=depth[:, 0], out_end=depth[:, 1],
        cnt=small[_CNT].astype(jnp.int32),
        prev_rgb=small[_PREV_RGB],
        prev_empty=small[_PREV_EMPTY] > 0.5)


def _phase_a(nc: int, rgba_of, thr, smi_, smo, ev_ref, kf):
    """Per-slice (slot, v) records from the shaded rgba stream
    (``rgba_of(s)`` f32[4, TH, WB]: read from the chunk's ref, or shaded
    here from the value plane); 4 small live carries and the two planes
    of the pixel's slot interval. Shared by both write kernels. Returns
    ``(first, last)`` f32[TH, WB]: the smallest and the largest slot a
    sample of this chunk landed in (``kf + 1`` and ``-1`` on a pixel
    with no live sample) — what `_phase_b_compact` bounds its loop by."""
    sm = smi_[...]
    run_cnt = sm[_CNT]
    pr = sm[_PREV_RGB]
    pe = sm[_PREV_EMPTY] > 0.5

    t_run = jnp.ones_like(thr)
    first = jnp.full_like(thr, kf + 1.0)
    last = jnp.full_like(thr, -1.0)
    for s in range(nc):
        rgba = rgba_of(s)
        emp = rgba[3] < ss.EMPTY_ALPHA
        d = rgba[:3] - pr
        diff = jnp.sqrt(jnp.sum(d * d, axis=0))
        start = ~emp & (pe | (diff > thr))
        run_cnt = run_cnt + start.astype(jnp.float32)
        sid = run_cnt - 1.0
        reset = start & (sid <= kf)
        t_here = jnp.where(reset, 1.0, t_run)
        t_run = t_here * (1.0 - jnp.where(emp, 0.0, rgba[3]))
        slotf = jnp.where(emp, -1.0, jnp.minimum(sid, kf))
        first = jnp.minimum(first, jnp.where(emp, kf + 1.0, slotf))
        last = jnp.maximum(last, slotf)
        v = rgba * (t_here * (~emp).astype(jnp.float32))[None]
        ev_ref[s] = jnp.concatenate([slotf[None], v])
        pr = jnp.where(emp[None], pr, rgba[:3])
        pe = emp

    smo[...] = jnp.concatenate([
        run_cnt[None], pr, pe.astype(jnp.float32)[None]])
    return first, last


def tile_slot_bounds(first, last, col0, width: int, max_k: int):
    """The slot rows ``[lo, hi)`` a tile's samples landed in, as two i32
    scalars with ``0 <= lo <= hi <= max_k`` whatever the planes hold.
    ``first`` / ``last`` f32[TH, BW] are `_phase_a`'s interval planes of
    the tile, whose lane 0 is the image's column ``col0``; lanes at or
    beyond ``width`` are a last block's padding (whatever was in VMEM,
    NaN included) and are left out, as is anything that is not a slot
    (negative, NaN). A tile with no live sample gives ``(0, 0)``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, first.shape, first.ndim - 1)
    inside = lane < width - col0
    lo = jnp.min(jnp.where(inside & (first >= 0.0), first, float(max_k)))
    hi = jnp.max(jnp.where(inside & (last >= 0.0), last, -1.0)) + 1.0
    hi = jnp.clip(hi, 0.0, float(max_k)).astype(jnp.int32)
    lo = jnp.clip(lo, 0.0, float(max_k)).astype(jnp.int32)
    return jnp.minimum(lo, hi), hi


def slot_tiles(height: int, width: int) -> int:
    """How many 8 x 128 tiles the fold counts slot rows by: a tile is a
    128-lane block inside the image's width (the last may be narrower)."""
    return (height // TILE_H) * pl.cdiv(width, TILE_W)


def _phase_b_compact(ev_ref, len_ref, sk0_ref, sk1_ref, ci_, di_, co,
                     do_, si_, so, first, last, max_k: int, width: int):
    """The K-loop merge shared by both feeds, its trip count following
    the data: per 8 x 128 tile of the strip, only the slot rows
    ``[lo, hi)`` some sample of the tile landed in (`tile_slot_bounds`,
    the hull of its pixels' intervals) masked-sum the per-slice records
    and under-merge into the aliased [K,...] state; the rows outside are
    copied, since the outputs are aliased but are their own VMEM buffers
    and an untouched row must still be written. Every row is visited
    once, so a dense tile costs what the plain ``fori_loop(0, K)`` did
    plus two reductions, and a tile with no live sample only copies. A
    128-lane block wholly beyond the image's width does nothing (its
    output lanes are dropped). The depth candidates are formed here from
    the per-slice ratios and the per-pixel ray length (t = sk * length —
    exactly the outer product a plane feed would materialize).

    ``si_`` / ``so`` i32[2, tiles] (SMEM, aliased): per tile of the
    image, the rows merged (``hi - lo``) and the rows visited
    (``max_k``), added up over the chunks the kernel ran on."""
    wb = len_ref.shape[-1]
    j, i = pl.program_id(0), pl.program_id(1)
    blocks = [(b0, min(TILE_W, wb - b0)) for b0 in range(0, wb, TILE_W)]
    # every tile's two vector-to-scalar reductions before the first loop
    # that waits on one
    bounds = [tile_slot_bounds(first[:, b0:b0 + bw], last[:, b0:b0 + bw],
                               i * wb + b0, width, max_k)
              for b0, bw in blocks]
    sk0, sk1 = sk0_ref[...], sk1_ref[...]

    for (b0, bw), (lo, hi) in zip(blocks, bounds):
        lanes = slice(b0, b0 + bw)

        @pl.when(i * wb + b0 < width)       # traced here, in the loop
        def _():
            ev = ev_ref[:, :, :, lanes]                    # [C, 5, TH, BW]
            ln = len_ref[:, lanes]                         # [TH, BW]
            t0a = sk0 * ln[None]                           # [C, TH, BW]
            t1a = sk1 * ln[None]
            ev_slot, ev_rgba = ev[:, 0], ev[:, 1:5]

            def slot_body(kk, _):
                m = ev_slot == kk.astype(jnp.float32)
                mf = m.astype(jnp.float32)
                contrib = jnp.sum(ev_rgba * mf[:, None], axis=0)
                d0 = jnp.min(jnp.where(m, t0a, jnp.inf), axis=0)
                d1 = jnp.max(jnp.where(m, t1a, -jnp.inf), axis=0)
                row = (pl.dslice(kk, 1), slice(None), slice(None), lanes)
                oc = ci_[row]
                co[row] = oc + (1.0 - oc[:, 3:4]) * contrib[None]
                dr = di_[row]
                do_[row] = jnp.stack(
                    [jnp.minimum(dr[0, 0], d0),
                     jnp.maximum(dr[0, 1], d1)])[None]
                return 0

            def copy_body(kk, _):
                row = (pl.dslice(kk, 1), slice(None), slice(None), lanes)
                co[row] = ci_[row]
                do_[row] = di_[row]
                return 0

            jax.lax.fori_loop(0, lo, copy_body, 0)
            jax.lax.fori_loop(lo, hi, slot_body, 0)
            jax.lax.fori_loop(hi, max_k, copy_body, 0)
            tile = (j * pl.cdiv(width, TILE_W) + i * (wb // TILE_W)
                    + b0 // TILE_W)
            so[0, tile] = si_[0, tile] + (hi - lo)
            so[1, tile] = si_[1, tile] + max_k


def _seg_kernel_compact(rgba_ref, len_ref, thr_ref, sk0_ref, sk1_ref,
                        ci_, di_, smi_, si_, co, do_, smo, so, ev_ref, *,
                        max_k: int, width: int):
    """The shaded feed: `_phase_a` reads the chunk's rgba as it came;
    the [C,2,H,W] depth planes never exist in HBM (`_phase_b_compact`)."""
    first, last = _phase_a(rgba_ref.shape[0], lambda s: rgba_ref[s],
                           thr_ref[...], smi_, smo, ev_ref,
                           jnp.float32(max_k - 1))
    _phase_b_compact(ev_ref, len_ref, sk0_ref, sk1_ref, ci_, di_, co, do_,
                     si_, so, first, last, max_k, width)


def fold_chunk_packed(packed, rgba: jnp.ndarray, threshold: jnp.ndarray, *,
                      max_k: int, sk0: jnp.ndarray, sk1: jnp.ndarray,
                      length: jnp.ndarray,
                      interpret: Optional[bool] = None):
    """Fold one SHADED chunk on VMEM pixel strips, packed-state in/out.

    ``packed`` is the `init_seg_packed` tuple; carrying it through the
    march's scan keeps the [K,...] state layout stable across chunks so
    ``input_output_aliases`` updates it in place — no per-chunk
    stack/slice re-materialization. rgba f32[C,4,H,W] premultiplied;
    ``sk0``/``sk1`` f32[C] per-slice depth ratios and ``length`` f32[H,W]:
    the kernel computes t = sk*length itself, so the [C,2,H,W] depth
    stream never exists in HBM. Semantics = seg_fold.seg_fold_chunk on
    the planes ``sk[:, None, None] * length``.
    """
    if interpret is None:
        interpret = should_interpret()
    color = packed[0]
    kk = color.shape[0]
    _, _, h, w = color.shape
    c = rgba.shape[0]
    if h % TILE_H:
        raise ValueError(f"height {h} not a multiple of {TILE_H}")
    threshold = jnp.broadcast_to(jnp.asarray(threshold, jnp.float32), (h, w))
    length = jnp.broadcast_to(jnp.asarray(length, jnp.float32), (h, w))
    sk0 = jnp.asarray(sk0, jnp.float32).reshape(c, 1, 1)
    sk1 = jnp.asarray(sk1, jnp.float32).reshape(c, 1, 1)

    # 4C rgba stream + 1 length plane; the kernel broadcasts its own
    # t0a/t1a [C,TH,WB] temporaries — counted in per_slice_records
    # exactly as _fused_fpp documents
    fpp = strip_fpp(c, kk, small_rows=_NSMALL, per_slice_records=7,
                    stream_per_slice=4, extra_planes=1)
    wb = pick_block_w(w, 4 * TILE_H * fpp)
    grid = (h // TILE_H, pl.cdiv(w, wb))
    row = lambda *lead: pl.BlockSpec(lead + (TILE_H, wb),
                                     lambda j, i: (0,) * len(lead) + (j, i))
    state_specs = [row(kk, 4), row(kk, 2), row(_NSMALL), _SLOTS_SPEC]
    sk_spec = pl.BlockSpec((c, 1, 1), lambda j, i: (0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_seg_kernel_compact, max_k=max_k, width=w),
        grid=grid,
        in_specs=[row(c, 4), row(), row(), sk_spec, sk_spec] + state_specs,
        out_specs=state_specs,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in packed],
        scratch_shapes=[pltpu.VMEM((c, 5, TILE_H, wb), jnp.float32)],
        input_output_aliases={5: 0, 6: 1, 7: 2, 8: 3},
        interpret=interpret,
        name="sitpu_fold_seg_compact",
    )(rgba, length, threshold, sk0, sk1, *packed)
    return tuple(out)


# ----------------------------------------------- fused shade+fold kernel


def tf_is_concrete(tf) -> bool:
    """Can the transfer function's knots be read as numbers here? Not
    where a caller jits over the TF: its leaves are tracers then."""
    return not any(isinstance(leaf, jax.core.Tracer)
                   for leaf in jax.tree_util.tree_leaves(tf))


def _tf_consts(tf) -> tuple:
    """The transfer function's knots as PYTHON floats, baked into the
    kernel as compile-time constants (zero-slope padded knots are skipped
    at kernel-build time — free TF trimming). Raises if the TF is traced:
    every production path closes over a concrete TF (the session rebuilds
    its compiled steps on a runtime TF swap), and a traced TF would need
    the knots as kernel operands — the generators give such a march the
    shaded feed instead (ops/slicer.fold_schedule)."""
    if not tf_is_concrete(tf):
        raise ValueError(
            "the fused fold kernel (pallas_fused) bakes the transfer "
            "function in and needs a CONCRETE "
            "TransferFunction, not traced values; pass the TF as a "
            "closure constant or fold the shaded chunk "
            "(fold_chunk_packed)")
    ax = np.asarray(tf.alpha_x).tolist()
    am = np.asarray(tf.alpha_m).tolist()
    ab = float(np.asarray(tf.alpha_b))
    cx = np.asarray(tf.color_x).tolist()
    cm = np.asarray(tf.color_m).tolist()
    cb = np.asarray(tf.color_b).tolist()
    return (tuple(ax), tuple(am), ab, tuple(cx),
            tuple(tuple(r) for r in cm), tuple(cb))


def _shade_plane(v_raw, ratio, tfc: tuple):
    """One value plane f32[TH, WB] (``-1`` = dead sample) -> premultiplied,
    opacity-corrected rgba f32[4, TH, WB]: `TransferFunction.__call__` in
    knot form with the knots as immediates (zero-slope padding knots
    compile to nothing), then `adjust_opacity`, formula-exact."""
    ax, am, ab, cx, cm, cb = tfc
    x = jnp.clip(v_raw, 0.0, 1.0)
    a = ab
    for xi, mi in zip(ax, am):
        if mi != 0.0:
            a = a + mi * jnp.maximum(x - xi, 0.0)
    chans = []
    for ch in range(3):
        cch = cb[ch]
        for xi, row in zip(cx, cm):
            if row[ch] != 0.0:
                cch = cch + row[ch] * jnp.maximum(x - xi, 0.0)
        chans.append(cch)
    a = jnp.where(v_raw < -0.5, 0.0, a)                    # dead sample
    a = 1.0 - jnp.power(jnp.clip(1.0 - a, 1e-7, 1.0), ratio)
    return jnp.stack([c * a for c in chans] + [a])


def _fused_kernel(val_ref, len_ref, ratio_ref, thr_ref, sk0_ref, sk1_ref,
                  ci_, di_, smi_, si_, co, do_, smo, so, ev_ref, *,
                  max_k: int, width: int, tfc: tuple):
    """Shade (TF + opacity correction) + segmented fold in ONE kernel —
    the TPU counterpart of the reference's fused generation kernel
    (VDIGenerator.comp:380-529 shades and accumulates per ray without
    leaving registers). Input is the 1-channel resampled value plane
    (sentinel -1 marks outside-volume/dead samples) instead of the
    4-channel post-TF rgba stream: 4x less HBM into the kernel, and the
    TF's relu-sum runs on VMEM-resident data with its knots baked in as
    immediates (`_tf_consts`). Past the shading it IS the compact seg
    kernel: the same `_phase_a` records, the same `_phase_b_compact`."""
    ratio = ratio_ref[...]

    def shade(s):
        return _shade_plane(val_ref[s], ratio, tfc)

    first, last = _phase_a(val_ref.shape[0], shade, thr_ref[...], smi_,
                           smo, ev_ref, jnp.float32(max_k - 1))
    _phase_b_compact(ev_ref, len_ref, sk0_ref, sk1_ref, ci_, di_, co, do_,
                     si_, so, first, last, max_k, width)


def _fused_fpp(c: int, k: int) -> int:
    """Fused-kernel strip budget via the shared formula: 1-channel value
    stream (vs the shaded feed's 4C), 2 extra per-pixel planes (length, ratio),
    and 7 per-slice record floats (5 scratch + the t0/t1 temporaries
    phase B broadcasts itself, as the shaded feed's)."""
    return strip_fpp(c, k, small_rows=_NSMALL, per_slice_records=7,
                     stream_per_slice=1, extra_planes=2)


def fused_fold_chunk(packed, val: jnp.ndarray, length: jnp.ndarray,
                     ratio: jnp.ndarray, sk0: jnp.ndarray,
                     sk1: jnp.ndarray, threshold: jnp.ndarray, *,
                     max_k: int, tf, interpret: Optional[bool] = None):
    """Fold one chunk straight from the resampled VALUE plane.

    val f32[C,H,W] with -1 sentinel for dead samples; length/ratio/
    threshold f32[H,W]; sk0/sk1 f32[C] per-slice depth ratios (t0/t1 =
    sk*length computed in-kernel — two full [C,H,W] depth streams never
    exist). ``tf`` must be a concrete TransferFunction (baked in)."""
    if interpret is None:
        interpret = should_interpret()
    tfc = _tf_consts(tf)
    color = packed[0]
    kk = color.shape[0]
    _, _, h, w = color.shape
    c = val.shape[0]
    if h % TILE_H:
        raise ValueError(f"height {h} not a multiple of {TILE_H}")
    threshold = jnp.broadcast_to(jnp.asarray(threshold, jnp.float32), (h, w))
    length = jnp.broadcast_to(jnp.asarray(length, jnp.float32), (h, w))
    ratio = jnp.broadcast_to(jnp.asarray(ratio, jnp.float32), (h, w))
    sk0 = jnp.asarray(sk0, jnp.float32).reshape(c, 1, 1)
    sk1 = jnp.asarray(sk1, jnp.float32).reshape(c, 1, 1)

    wb = pick_block_w(w, 4 * TILE_H * _fused_fpp(c, kk))
    grid = (h // TILE_H, pl.cdiv(w, wb))
    row = lambda *lead: pl.BlockSpec(lead + (TILE_H, wb),
                                     lambda j, i: (0,) * len(lead) + (j, i))
    state_specs = [row(kk, 4), row(kk, 2), row(_NSMALL), _SLOTS_SPEC]
    sk_spec = pl.BlockSpec((c, 1, 1), lambda j, i: (0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_fused_kernel, max_k=max_k, width=w, tfc=tfc),
        grid=grid,
        in_specs=[row(c), row(), row(), row(), sk_spec, sk_spec]
        + state_specs,
        out_specs=state_specs,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in packed],
        scratch_shapes=[pltpu.VMEM((c, 5, TILE_H, wb), jnp.float32)],
        input_output_aliases={6: 0, 7: 1, 8: 2, 9: 3},
        interpret=interpret,
        name="sitpu_fold_fused",
    )(val, length, ratio, threshold, sk0, sk1, *packed)
    return tuple(out)


# ------------------------------------------------------- histogram counting


# the count kernel's strip estimate treats the bins as at least this
# many, so the block width is the same for every bins <= _EST_B
_EST_B = 32


def _count_kernel(rgba_ref, tvec_ref, cnt_i, prev_i, fe_i,
                  cnt_o, prev_o, fe_o):
    nc = rgba_ref.shape[0]
    thr = tvec_ref[...]                                    # [B, 1, 1]
    cnt_o[...] = cnt_i[...]
    prev_o[...] = prev_i[...]
    fe_o[...] = fe_i[...]

    def body(i, _):
        rgba = rgba_ref[i]
        starts, is_empty = ss._start_mask(prev_o[...], fe_o[...] > 0.5,
                                          None, rgba, thr, None, -1.0)
        cnt_o[...] = cnt_o[...] + starts.astype(jnp.int32)
        prev_o[...] = jnp.where(is_empty[None], prev_o[...], rgba[:3])
        fe_o[...] = is_empty.astype(jnp.float32)
        return 0

    jax.lax.fori_loop(0, nc, body, 0)


def count_multi_chunk(carry, rgba: jnp.ndarray, tvec, *,
                      interpret: Optional[bool] = None):
    """One chunk of the all-candidates counting march (≅ feeding
    `ss.init_count_multi` state through `ss.push_count` with
    ``threshold=tvec[:,None,None]``, VMEM-tiled). ``carry`` is
    ``(count i32[B,H,W], prev f32[3,H,W], prev_empty f32[H,W])``;
    ``tvec`` is the B candidate thresholds (any array-like; a pallas
    kernel cannot close over array constants, so they ride as a [B,1,1]
    input).
    """
    if interpret is None:
        interpret = should_interpret()
    count, prev, fe = carry
    b, h, w = count.shape
    c = rgba.shape[0]
    if h % TILE_H:
        raise ValueError(f"height {h} not a multiple of {TILE_H}")
    tvec3 = jnp.asarray(tvec, jnp.float32).reshape(b, 1, 1)

    floats_per_px = 2 * 2 * (4 * c + 2 * (max(b, _EST_B) + 4)) + 32
    wb = pick_block_w(w, 4 * TILE_H * floats_per_px)
    row = lambda *lead: pl.BlockSpec(lead + (TILE_H, wb),
                                     lambda j, i: (0,) * len(lead) + (j, i))
    out = pl.pallas_call(
        _count_kernel, grid=(h // TILE_H, pl.cdiv(w, wb)),
        in_specs=[row(c, 4),
                  pl.BlockSpec((b, 1, 1), lambda j, i: (0, 0, 0)),
                  row(b), row(3), row()],
        out_specs=[row(b), row(3), row()],
        out_shape=[jax.ShapeDtypeStruct((b, h, w), jnp.int32),
                   jax.ShapeDtypeStruct((3, h, w), jnp.float32),
                   jax.ShapeDtypeStruct((h, w), jnp.float32)],
        input_output_aliases={2: 0, 3: 1, 4: 2},
        interpret=interpret,
        name="sitpu_fold_count",
    )(rgba, tvec3, count, prev, fe)
    return tuple(out)


def init_count_multi_packed(bins: int, height: int, width: int):
    return (jnp.zeros((bins, height, width), jnp.int32),
            jnp.zeros((3, height, width), jnp.float32),
            jnp.ones((height, width), jnp.float32))
