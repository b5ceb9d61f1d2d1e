"""Pallas TPU kernels for the slice-march supersegment folds — the fused
counterpart of the reference's single-kernel generation (VDIGenerator.comp:
380-529 + AccumulateVDI.comp:69-98, where raycast sampling and the
supersegment state machine live in ONE GPU kernel and the per-ray state
never leaves registers).

The XLA march (ops/slicer.slice_march + ops/supersegments.push) carries the
full ``SegState`` — ~107 floats per pixel, dominated by ``out_color
[K,4,H,W]`` — through a ``lax.scan``, and every per-slice ``push`` inside
the scan body reads and rewrites those full-frame tensors through HBM.

The first fused kernel (round 3, commit 2358581) moved that fold onto VMEM
pixel strips but kept the XLA fold's schedule: per SLICE, load the whole
packed K-state from the VMEM refs, run ``ss.push`` (whose ``_write`` does
an O(K) one-hot select over every [K,...] array), store the whole state
back. On real hardware that was a regression — the 2026-07-30 TPU captures
(benchmarks/results/bench_tpu_r3_*.json) put the write march at ~390 ms at
512^3 vs ~34 ms for the O(1)-state counting march: ~100 floats/pixel of
VMEM state round-tripped per slice drowns the ~30-op state machine.

This kernel therefore splits the fold into two phases with the K-state
touched ONCE per chunk (benchmarks/fold_microbench.py measures the
schedules side by side):

- **Phase 1** unrolls the C-slice loop with the O(1) segment machine
  (open-segment RGBA/extent, prev-item, slot counter — 12 floats/pixel)
  carried as SSA values (registers; Mosaic spills what doesn't fit), and
  records each slice's potential close event (slot, rgba, t0, t1) as
  values. The optional temporal start-count accumulates here for free —
  it shares the writer's own prev-item stream exactly like the XLA
  ``ss.push_count`` twin.
- **Phase 2** loops over the K output slots; each slot row sums its (at
  most one — slots close at most once per march, the counter only moves
  forward) matching event from the C records and merges with the incoming
  row. [K,...] state: one read + one write per chunk.

Both phases implement exactly ``ss.push``'s semantics (same predicates,
same merge-overflow into the last slot); tests/test_pallas_march.py and
the committed golden fixture (tests/test_golden.py) pin equality with the
XLA fold chunk by chunk.

State is packed into 3 arrays: ``color f32[K,4,H,W]``, ``depth
f32[K,2,H,W]`` (start/end in [:,0]/[:,1]), and ``small f32[12,H,W]`` =
seg_rgba[0:4], seg_start[4], seg_end[5], prev_rgb[6:9], open[9],
prev_empty[10], k-count[11] (f32-encoded). ``input_output_aliases`` pins
each state input to its output so XLA updates in place.

Tiling: (8, WB) strips — 8 sublanes × a width block, grid over
(H/8, ceil(W/WB)). WB is the full row when the strip's VMEM estimate fits
the scoped budget (320-wide frames keep the round-2 single-block schedule)
and otherwise the largest multiple of 128 that does: at the 512^3 bench
scale (W=640, K=C=16) the full-width strip demands 16.39 MB scoped VMEM
against Mosaic's 16 MB limit — over by 2.5% — and the standalone compile
probe passes while the same kernel embedded in the frame's while/cond
fails on the extra stack frames, so the geometry must leave headroom
rather than ride the limit. W needn't be a multiple of the block: the
last block's lane padding is masked by Mosaic and no HBM copy is spent on
alignment. H must be a multiple of 8 (`slicer.make_spec` guarantees it).
On CPU (tests, the virtual mesh) the kernels run in interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from scenery_insitu_tpu.ops import supersegments as ss
from scenery_insitu_tpu.ops.pallas_util import TILE_H, should_interpret

# packed-state arrays: color, depth, small
_STATE_FIELDS = 3
# small-state rows
_SEG_RGBA = slice(0, 4)
_SEG_START, _SEG_END = 4, 5
_PREV_RGB = slice(6, 9)
_OPEN, _PREV_EMPTY, _K = 9, 10, 11
_NSMALL = 12


# VMEM budget the strip ESTIMATE must fit in. The estimate is deliberately
# conservative — ~1.65x the 16.39 MB Mosaic measured for the K=16/C=16
# 640-wide strip (scoped-vmem error, window 2) — so 14 MB of estimate is
# ~8.5 MB of true usage: ample headroom under the 16 MB scoped limit for
# Mosaic's stack frames when the kernel sits inside lax control flow (the
# 512^3 OOM rode the limit and lost by 404 KB). 14 MB is calibrated so the
# default-config 320-wide strip (estimate 13.5 MB, true ~8.4 MB) keeps the
# round-2 single-block schedule the window-2 microbench numbers were
# captured under, while 640-wide strips tile to wb=256.
_VMEM_STRIP_BUDGET = 14 * 1024 * 1024
# geometry override for benchmarks/fold_microbench.py's hardware sweeps;
# None = budget-driven choice
_FORCE_BLOCK_W: Optional[int] = None
# fold_chunk's VMEM estimate treats K as at least this value, so the block
# width is the same for every K <= _EST_K; larger K shrinks the block.
_EST_K = 32
# bins floor for the counting kernel's block-width estimate, likewise
_EST_B = 32
# phase-2 schedule experiment (benchmarks/fold_microbench.py variant
# "pallas_gated"): skip the event-extraction math for slot rows with no
# close event anywhere in the block — a chunk typically closes only a few
# consecutive slots per pixel, so most of the K x C extraction work sums
# zeros. Off by default until hardware shows it wins (the gate adds a
# scalar reduction + branch per slot row, and Mosaic's lowering cost for
# that is unknown).
_PHASE2_GATED = False


def strip_fpp(c: int, k: int, small_rows: int = _NSMALL,
              count_plane: bool = True, per_slice_records: int = 7,
              stream_per_slice: int = 6, extra_planes: int = 0) -> int:
    """Strip VMEM estimate in floats per pixel column — THE one budget
    formula every fold kernel and its microbench twins share: in+out
    blocks double-buffered (x2x2) over (stream_per_slice*C stream +
    1 threshold + extra per-pixel planes + 6K state + small rows +
    optional count plane), plus the per-slice record arrays (events or
    seg (slot,v) records) and slack for phase temporaries. K floored at
    _EST_K. Callers differing from the
    production fold pass their deltas explicitly instead of hand-copying
    the formula."""
    return (2 * 2 * (stream_per_slice * c + 1 + extra_planes
                     + 6 * max(k, _EST_K) + small_rows
                     + (1 if count_plane else 0))
            + per_slice_records * c + 64)


def _pick_block_w(w: int, bytes_per_col: int) -> int:
    """Widest block (full row, else a multiple of 128 lanes) whose strip
    VMEM estimate stays under the budget. ``bytes_per_col`` is the
    estimate for one pixel column of the strip (all TILE_H rows)."""
    if _FORCE_BLOCK_W is not None:
        return min(w, _FORCE_BLOCK_W)
    if w * bytes_per_col <= _VMEM_STRIP_BUDGET:
        return w
    wb = (_VMEM_STRIP_BUDGET // bytes_per_col) // 128 * 128
    if wb < 128:
        from scenery_insitu_tpu import obs

        obs.degrade(
            "ops.pallas_march.block_width", "budgeted strip",
            "128-lane floor",
            f"strip needs {bytes_per_col * 128 / 2**20:.1f} MB VMEM at "
            "the 128-lane minimum block width — over the "
            f"{_VMEM_STRIP_BUDGET / 2**20:.0f} MB budget; compiling at "
            "the floor anyway (Mosaic may refuse it)", stacklevel=3)
    return max(128, min(wb, w))


# ------------------------------------------------------------- state packing


def init_packed(k: int, height: int, width: int):
    """Packed fold state ≅ ss.init_state(k, height, width)."""
    color = jnp.zeros((k, 4, height, width), jnp.float32)
    depth = jnp.full((k, 2, height, width), jnp.inf, jnp.float32)
    small = jnp.zeros((_NSMALL, height, width), jnp.float32)
    small = small.at[_PREV_EMPTY].set(1.0)
    return (color, depth, small)


def pack_state(st: ss.SegState):
    small = jnp.concatenate([
        st.seg_rgba,
        st.seg_start[None], st.seg_end[None],
        st.prev_rgb,
        st.open_.astype(jnp.float32)[None],
        st.prev_empty.astype(jnp.float32)[None],
        st.k.astype(jnp.float32)[None]])
    return (st.out_color,
            jnp.stack([st.out_start, st.out_end], axis=1),
            small)


def unpack_state(packed) -> ss.SegState:
    color, depth, small = packed
    return ss.SegState(
        out_color=color, out_start=depth[:, 0], out_end=depth[:, 1],
        k=small[_K].astype(jnp.int32), open_=small[_OPEN] > 0.5,
        seg_rgba=small[_SEG_RGBA],
        seg_start=small[_SEG_START], seg_end=small[_SEG_END],
        prev_rgb=small[_PREV_RGB], prev_empty=small[_PREV_EMPTY] > 0.5)


# ------------------------------------------------------------ write(+count)


def _fold_kernel(*refs, max_k: int, gap_eps: float, with_count: bool):
    if with_count:
        (rgba_ref, td_ref, thr_ref,
         ci_, di_, smi_, cnt_i,
         co, do_, smo, cnt_o) = refs
    else:
        (rgba_ref, td_ref, thr_ref,
         ci_, di_, smi_,
         co, do_, smo) = refs
        cnt_i = cnt_o = None
    nc = rgba_ref.shape[0]
    thr = thr_ref[...]

    # ---- phase 1: O(1) machine over the C slices, state in SSA values
    sm = smi_[...]
    seg_rgba = sm[_SEG_RGBA]
    seg_start, seg_end = sm[_SEG_START], sm[_SEG_END]
    prev_rgb = sm[_PREV_RGB]
    open_ = sm[_OPEN] > 0.5
    prev_empty = sm[_PREV_EMPTY] > 0.5
    kcnt = sm[_K]
    n_starts = None

    events = []                        # (slot f32, rgba [4], t0, t1)
    for i in range(nc):
        rgba = rgba_ref[i]
        t0 = td_ref[i, 0]
        t1 = td_ref[i, 1]
        is_empty = rgba[3] < ss.EMPTY_ALPHA
        d = rgba[:3] - prev_rgb
        diff = jnp.sqrt(jnp.sum(d * d, axis=0))
        break_metric = ~is_empty & ~prev_empty & (diff > thr)
        want_break = break_metric | (is_empty & ~prev_empty)
        if gap_eps >= 0.0:
            want_break |= ~is_empty & open_ & (t0 > seg_end + gap_eps)
        do_close = open_ & want_break & (kcnt < max_k - 1)
        if with_count:
            # TRUE segment starts at this threshold (temporal feedback):
            # ss.push_count's predicate on the writer's prev-item stream
            starts = ~is_empty & (prev_empty | (diff > thr))
            sf = starts.astype(jnp.float32)
            n_starts = sf if n_starts is None else n_starts + sf
        events.append((jnp.where(do_close, kcnt, -1.0),
                       jnp.where(do_close[None], seg_rgba, 0.0),
                       jnp.where(do_close, seg_start, 0.0),
                       jnp.where(do_close, seg_end, 0.0)))
        kcnt = jnp.where(do_close, kcnt + 1.0, kcnt)
        open_ = open_ & ~do_close
        start_new = ~is_empty & ~open_
        accumulate = ~is_empty & open_
        seg_rgba = jnp.where(
            start_new[None], rgba,
            jnp.where(accumulate[None],
                      seg_rgba + (1.0 - seg_rgba[3:4]) * rgba, seg_rgba))
        seg_start = jnp.where(start_new, t0, seg_start)
        seg_end = jnp.where(start_new | accumulate, t1, seg_end)
        open_ = open_ | start_new
        prev_rgb = jnp.where(is_empty[None], prev_rgb, rgba[:3])
        prev_empty = is_empty

    smo[...] = jnp.concatenate([
        seg_rgba, seg_start[None], seg_end[None], prev_rgb,
        open_.astype(jnp.float32)[None],
        prev_empty.astype(jnp.float32)[None], kcnt[None]])
    if with_count:
        cnt_o[...] = cnt_i[...] + n_starts.astype(jnp.int32)

    # ---- phase 2: per-slot event extraction; K-state touched once.
    # Rolled over K (the event arrays are loop-INVARIANT captures — only
    # carried state breaks Mosaic legalization) so the kernel graph stays
    # small: the unrolled K×C version compiled ~4× slower everywhere and
    # dominated interpret-mode test time.
    ev_slot = jnp.stack([e[0] for e in events])            # [C, TH, W]
    ev_rgba = jnp.stack([e[1] for e in events])            # [C, 4, TH, W]
    ev_s = jnp.stack([e[2] for e in events])               # [C, TH, W]
    ev_e = jnp.stack([e[3] for e in events])               # [C, TH, W]

    def _extract(kk):
        m = ev_slot == kk.astype(jnp.float32)
        mf = m.astype(jnp.float32)
        hit = jnp.any(m, axis=0)
        acc_c = jnp.sum(ev_rgba * mf[:, None], axis=0)
        acc_s = jnp.sum(ev_s * mf, axis=0)
        acc_e = jnp.sum(ev_e * mf, axis=0)
        # + is a select: a slot closes at most once over the whole march
        # (the counter only moves forward), and color rows start at 0;
        # depth rows start at +inf so they need the explicit where
        co[pl.dslice(kk, 1)] = (ci_[pl.dslice(kk, 1)]
                                + acc_c[None].astype(jnp.float32))
        drow = di_[pl.dslice(kk, 1)]
        do_[pl.dslice(kk, 1)] = jnp.stack(
            [jnp.where(hit, acc_s, drow[0, 0]),
             jnp.where(hit, acc_e, drow[0, 1])])[None]

    def _copy_row(kk):
        co[pl.dslice(kk, 1)] = ci_[pl.dslice(kk, 1)]
        do_[pl.dslice(kk, 1)] = di_[pl.dslice(kk, 1)]

    if _PHASE2_GATED:
        # a row with no event anywhere in the block only needs the
        # passthrough copy (the out block must still be fully written —
        # it is a fresh VMEM buffer, not the input). NOTE: the jnp.any
        # reduces over the WHOLE block including the masked lane padding
        # of a partial last block on hardware; garbage in the padding can
        # only flip the gate CONSERVATIVELY true (extract where a copy
        # would do — correct, just slower), so a flat gated-vs-ungated
        # hardware result on non-128-multiple widths must not be misread
        # as the gate being worthless. Untestable in interpret mode.
        def slot_body(kk, _):
            kf = kk.astype(jnp.float32)
            row_has_event = jnp.any(ev_slot == kf)
            jax.lax.cond(row_has_event, _extract, _copy_row, kk)
            return 0
    else:
        def slot_body(kk, _):
            _extract(kk)
            return 0

    jax.lax.fori_loop(0, max_k, slot_body, 0)


def fold_chunk(packed, rgba: jnp.ndarray, t0: jnp.ndarray, t1: jnp.ndarray,
               threshold: jnp.ndarray, *, max_k: int,
               count: Optional[jnp.ndarray] = None, gap_eps: float = -1.0,
               interpret: Optional[bool] = None):
    """Fold one chunk of slices through the writer machine on pixel strips.

    packed: `pack_state` triple (color [K,4,H,W], depth [K,2,H,W], small
    [12,H,W]); rgba f32[C,4,H,W] premultiplied; t0/t1 f32[C,H,W];
    threshold f32[H,W] (or scalar). ``count`` (i32[H,W], optional)
    additionally accumulates TRUE segment starts at this threshold (the
    temporal controller's signal). Returns the updated packed state (and
    count when given) — bit-identical to C sequential
    ``ss.push``/``ss.push_count`` calls.
    """
    if interpret is None:
        interpret = should_interpret()
    color, depth, small = packed
    kk = color.shape[0]
    _, _, h, w = color.shape
    c = rgba.shape[0]
    if h % TILE_H:
        raise ValueError(f"height {h} not a multiple of {TILE_H}")
    threshold = jnp.broadcast_to(jnp.asarray(threshold, jnp.float32), (h, w))
    td = jnp.stack([t0, t1], axis=1)                       # [C, 2, H, W]
    with_count = count is not None

    # the count plane is budgeted whether or not it rides along, so both
    # variants tile alike
    wb = _pick_block_w(w, 4 * TILE_H * strip_fpp(c, kk))
    grid = (h // TILE_H, pl.cdiv(w, wb))
    row = lambda *lead: pl.BlockSpec(lead + (TILE_H, wb),
                                     lambda j, i: (0,) * len(lead) + (j, i))
    state_specs = [row(kk, 4), row(kk, 2), row(_NSMALL)]
    state_shapes = [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in packed]
    in_specs = [row(c, 4), row(c, 2), row()] + list(state_specs)
    out_specs = list(state_specs)
    out_shapes = list(state_shapes)
    operands = [rgba, td, threshold, *packed]
    # state input i+3 aliases output i (in-place update under jit)
    aliases = {i + 3: i for i in range(_STATE_FIELDS)}
    if with_count:
        in_specs.append(row())
        out_specs.append(row())
        out_shapes.append(jax.ShapeDtypeStruct((h, w), jnp.int32))
        operands.append(count)
        aliases[3 + _STATE_FIELDS] = _STATE_FIELDS

    kernel = functools.partial(_fold_kernel, max_k=max_k, gap_eps=gap_eps,
                               with_count=with_count)
    out = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shapes, input_output_aliases=aliases,
        interpret=interpret,
        name="sitpu_fold_push",
    )(*operands)
    if with_count:
        return tuple(out[:_STATE_FIELDS]), out[_STATE_FIELDS]
    return tuple(out)


# ------------------------------------------------------- histogram counting


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

    # b floored at _EST_B so the block width is identical for every
    # bins <= _EST_B
    floats_per_px = 2 * 2 * (4 * c + 2 * (max(b, _EST_B) + 4)) + 32
    wb = _pick_block_w(w, 4 * TILE_H * floats_per_px)
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
