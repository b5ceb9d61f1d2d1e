"""Pallas TPU kernel for the segmented-scan write fold (ops/seg_fold.py).

Same algorithm as the XLA schedule — parallel start flags, segment ids by
running count, segmented transmittance, K masked reductions — with the
memory movement pinned down: the sample chunk, the K-slot state and the
per-slice ``(slot, v)`` records all live in VMEM pixel strips, and the
``[K,...]`` state crosses HBM once per chunk via ``input_output_aliases``.

Contrast with the round-3 two-phase kernel (ops/pallas_march.py), which
kept the *sequential* ``ss.push`` machine and deferred 7×C close-event
values across the whole unrolled slice loop as SSA live ranges — the
hardware-measured suspect for its 300×-above-floor cost. Here phase A
carries just four small values per pixel between slices (running start
count, running transmittance, prev rgb, prev empty) and writes each
slice's ``(slot, premultiplied-scaled rgba)`` record straight to a VMEM
scratch ref, so no live range spans the loop; phase B re-reads the
scratch per slot row — VMEM-to-register traffic, not HBM.

Semantics are identical to ``seg_fold.seg_fold_chunk`` (tests pin
interpret-mode equality) and therefore to C sequential ``ss.push`` calls
up to fp association (≅ the reference's fused single-kernel generation,
VDIGenerator.comp:380-529 + AccumulateVDI.comp:69-98).

State layout (3 aliased arrays, same convention as pallas_march):
``color f32[K,4,H,W]``, ``depth f32[K,2,H,W]`` (start/end; start init
+inf, end init -inf), ``small f32[5,H,W]`` = cnt[0] (f32-encoded),
prev_rgb[1:4], prev_empty[4]. Helpers convert to/from
``seg_fold.SegFoldState`` so the march code handles ONE state type.
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
from scenery_insitu_tpu.ops.pallas_march import _pick_block_w, strip_fpp
from scenery_insitu_tpu.ops.pallas_util import TILE_H, should_interpret

_CNT, _PREV_RGB, _PREV_EMPTY = 0, slice(1, 4), 4
_NSMALL = 5


def init_seg_packed(k: int, height: int, width: int):
    """Packed fold state ≅ seg_fold.init_seg_state — built directly in
    packed layout so a march can carry the triple through its scan with
    no per-chunk stack/concat traffic (the depth plane alone is
    [K,2,H,W]; re-materializing it every chunk would cost more HBM than
    the kernel's own state pass)."""
    color = jnp.zeros((k, 4, height, width), jnp.float32)
    depth = jnp.stack([
        jnp.full((k, height, width), jnp.inf, jnp.float32),
        jnp.full((k, height, width), -jnp.inf, jnp.float32)], axis=1)
    small = jnp.zeros((_NSMALL, height, width), jnp.float32)
    small = small.at[_PREV_EMPTY].set(1.0)
    return (color, depth, small)


def pack_seg_state(st: sf.SegFoldState):
    small = jnp.concatenate([
        st.cnt.astype(jnp.float32)[None],
        st.prev_rgb,
        st.prev_empty.astype(jnp.float32)[None]])
    return (st.out_color,
            jnp.stack([st.out_start, st.out_end], axis=1),
            small)


def unpack_seg_state(packed) -> sf.SegFoldState:
    color, depth, small = packed
    return sf.SegFoldState(
        out_color=color, out_start=depth[:, 0], out_end=depth[:, 1],
        cnt=small[_CNT].astype(jnp.int32),
        prev_rgb=small[_PREV_RGB],
        prev_empty=small[_PREV_EMPTY] > 0.5)


def _phase_b(ev_slot, ev_rgba, t0_of, t1_of, ci_, di_, co, do_,
             max_k: int):
    """Rolled K-loop merge shared by the seg and fused kernels: per slot
    row, masked-sum the per-slice records and under-merge into the
    aliased [K,...] state (touched once per chunk). ``t0_of(m)``/
    ``t1_of(m)`` produce the masked depth candidates for a slot mask so
    each kernel can source depths from its own layout."""
    def slot_body(kk, _):
        m = ev_slot == kk.astype(jnp.float32)
        mf = m.astype(jnp.float32)
        contrib = jnp.sum(ev_rgba * mf[:, None], axis=0)
        d0 = jnp.min(t0_of(m), axis=0)
        d1 = jnp.max(t1_of(m), axis=0)
        oc = ci_[pl.dslice(kk, 1)]
        co[pl.dslice(kk, 1)] = oc + (1.0 - oc[:, 3:4]) * contrib[None]
        dr = di_[pl.dslice(kk, 1)]
        do_[pl.dslice(kk, 1)] = jnp.stack(
            [jnp.minimum(dr[0, 0], d0), jnp.maximum(dr[0, 1], d1)])[None]
        return 0

    jax.lax.fori_loop(0, max_k, slot_body, 0)


def _phase_a(nc: int, rgba_of, thr, smi_, smo, ev_ref, kf):
    """Per-slice (slot, v) records from the shaded rgba stream
    (``rgba_of(s)`` f32[4, TH, WB]: read from the chunk's ref, or shaded
    here from the value plane); 4 small live carries. Shared by every
    kernel of this module."""
    sm = smi_[...]
    run_cnt = sm[_CNT]
    pr = sm[_PREV_RGB]
    pe = sm[_PREV_EMPTY] > 0.5

    t_run = jnp.ones_like(thr)
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
        v = rgba * (t_here * (~emp).astype(jnp.float32))[None]
        ev_ref[s] = jnp.concatenate([slotf[None], v])
        pr = jnp.where(emp[None], pr, rgba[:3])
        pe = emp

    smo[...] = jnp.concatenate([
        run_cnt[None], pr, pe.astype(jnp.float32)[None]])


def _phase_b_compact(ev_ref, len_ref, sk0_ref, sk1_ref, ci_, di_, co,
                     do_, max_k: int):
    """`_phase_b` with the depth candidates formed here from the
    per-slice ratios and the per-pixel ray length (t = sk * length —
    exactly what the march's outer product materialized)."""
    ev = ev_ref[...]                                       # [C, 5, TH, WB]
    ln = len_ref[...]                                      # [TH, WB]
    t0a = sk0_ref[...] * ln[None]                          # [C, TH, WB]
    t1a = sk1_ref[...] * ln[None]
    _phase_b(ev[:, 0], ev[:, 1:5],
             lambda m: jnp.where(m, t0a, jnp.inf),
             lambda m: jnp.where(m, t1a, -jnp.inf),
             ci_, di_, co, do_, max_k)


def _seg_kernel(rgba_ref, td_ref, thr_ref, ci_, di_, smi_,
                co, do_, smo, ev_ref, *, max_k: int):
    _phase_a(rgba_ref.shape[0], lambda s: rgba_ref[s], thr_ref[...],
             smi_, smo, ev_ref, jnp.float32(max_k - 1))

    # ---- phase B: rolled K loop, state touched once per chunk
    ev = ev_ref[...]                                       # [C, 5, TH, WB]
    _phase_b(ev[:, 0], ev[:, 1:5],
             lambda m: jnp.where(m, td_ref[:, 0], jnp.inf),
             lambda m: jnp.where(m, td_ref[:, 1], -jnp.inf),
             ci_, di_, co, do_, max_k)


def _seg_kernel_compact(rgba_ref, len_ref, thr_ref, sk0_ref, sk1_ref,
                        ci_, di_, smi_, co, do_, smo, ev_ref, *,
                        max_k: int):
    """_seg_kernel with the depth planes computed IN-KERNEL
    (`_phase_b_compact`): the [C,2,H,W] td stream never exists in HBM,
    the march's biggest remaining stream term after rgba (~3.4 GB/march
    at the 512³ flagship)."""
    _phase_a(rgba_ref.shape[0], lambda s: rgba_ref[s], thr_ref[...],
             smi_, smo, ev_ref, jnp.float32(max_k - 1))
    _phase_b_compact(ev_ref, len_ref, sk0_ref, sk1_ref, ci_, di_, co, do_,
                     max_k)


def fold_chunk_packed(packed, rgba: jnp.ndarray, t0=None, t1=None,
                      threshold: jnp.ndarray = None, *, max_k: int,
                      interpret: Optional[bool] = None,
                      sk0=None, sk1=None, length=None):
    """Fold one chunk on VMEM pixel strips, packed-state in/out.

    ``packed`` is the `init_seg_packed` triple; carrying it through the
    march's scan keeps the [K,...] state layout stable across chunks so
    ``input_output_aliases`` updates it in place — no per-chunk
    stack/slice re-materialization. Semantics = seg_fold.seg_fold_chunk.

    Depth comes in one of two forms:
    - ``t0``/``t1`` f32[C,H,W] planes (tests / arbitrary streams), or
    - COMPACT: ``sk0``/``sk1`` f32[C] per-slice ratios + ``length``
      f32[H,W] — the kernel computes t = sk*length itself, so the
      [C,2,H,W] depth stream never exists in HBM (the production march
      path; its t0/t1 are exactly this outer product).
    """
    if interpret is None:
        interpret = should_interpret()
    planes_any = t0 is not None or t1 is not None
    compact_any = (sk0 is not None or sk1 is not None
                   or length is not None)
    planes_full = t0 is not None and t1 is not None
    compact_full = (sk0 is not None and sk1 is not None
                    and length is not None)
    if planes_any and compact_any:
        raise ValueError("depth forms cannot be mixed: got t0/t1 plane "
                         "args together with sk0/sk1/length compact args")
    if not (planes_full or compact_full):
        raise ValueError("pass exactly one COMPLETE depth form: "
                         "(t0, t1) or (sk0, sk1, length)")
    compact = compact_full
    color, depth, small = packed
    kk = color.shape[0]
    _, _, h, w = color.shape
    c = rgba.shape[0]
    if h % TILE_H:
        raise ValueError(f"height {h} not a multiple of {TILE_H}")
    threshold = jnp.broadcast_to(jnp.asarray(threshold, jnp.float32), (h, w))

    # compact: the rgba stream shrinks 6C->4C and gains 1 length plane,
    # but the kernel broadcasts its own t0a/t1a [C,TH,WB] temporaries —
    # counted in per_slice_records exactly as _fused_fpp documents
    fpp = strip_fpp(c, kk, small_rows=_NSMALL, count_plane=False,
                    per_slice_records=7 if compact else 5,
                    stream_per_slice=4 if compact else 6,
                    extra_planes=1 if compact else 0)
    wb = _pick_block_w(w, 4 * TILE_H * fpp)
    grid = (h // TILE_H, pl.cdiv(w, wb))
    row = lambda *lead: pl.BlockSpec(lead + (TILE_H, wb),
                                     lambda j, i: (0,) * len(lead) + (j, i))
    state_specs = [row(kk, 4), row(kk, 2), row(_NSMALL)]
    if compact:
        length = jnp.broadcast_to(jnp.asarray(length, jnp.float32), (h, w))
        sk0 = jnp.asarray(sk0, jnp.float32).reshape(c, 1, 1)
        sk1 = jnp.asarray(sk1, jnp.float32).reshape(c, 1, 1)
        sk_spec = pl.BlockSpec((c, 1, 1), lambda j, i: (0, 0, 0))
        out = pl.pallas_call(
            functools.partial(_seg_kernel_compact, max_k=max_k),
            grid=grid,
            in_specs=[row(c, 4), row(), row(), sk_spec, sk_spec]
            + state_specs,
            out_specs=state_specs,
            out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype)
                       for p in packed],
            scratch_shapes=[pltpu.VMEM((c, 5, TILE_H, wb), jnp.float32)],
            input_output_aliases={5: 0, 6: 1, 7: 2},
            interpret=interpret,
            name="sitpu_fold_seg_compact",
        )(rgba, length, threshold, sk0, sk1, *packed)
        return tuple(out)

    td = jnp.stack([t0, t1], axis=1)                       # [C, 2, H, W]
    out = pl.pallas_call(
        functools.partial(_seg_kernel, max_k=max_k),
        grid=grid,
        in_specs=[row(c, 4), row(c, 2), row()] + state_specs,
        out_specs=state_specs,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in packed],
        scratch_shapes=[pltpu.VMEM((c, 5, TILE_H, wb), jnp.float32)],
        input_output_aliases={3: 0, 4: 1, 5: 2},
        interpret=interpret,
        name="sitpu_fold_seg",
    )(rgba, td, threshold, *packed)
    return tuple(out)


def seg_fold_chunk(st: sf.SegFoldState, rgba: jnp.ndarray, t0: jnp.ndarray,
                   t1: jnp.ndarray, threshold: jnp.ndarray, *, max_k: int,
                   interpret: Optional[bool] = None) -> sf.SegFoldState:
    """Drop-in twin of ``seg_fold.seg_fold_chunk`` (NamedTuple in/out).
    Convenience for tests/small streams — production marches carry the
    packed triple via `init_seg_packed` + `fold_chunk_packed` instead,
    avoiding the pack/unpack copies this wrapper pays per call."""
    packed = pack_seg_state(st)
    out = fold_chunk_packed(packed, rgba, t0, t1, threshold, max_k=max_k,
                            interpret=interpret)
    return unpack_seg_state(out)


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
            "the fused fold kernels (pallas_fused / fused_stream) bake "
            "the transfer function in and need a CONCRETE "
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
                  ci_, di_, smi_, co, do_, smo, ev_ref, *,
                  max_k: int, tfc: tuple):
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

    _phase_a(val_ref.shape[0], shade, thr_ref[...], smi_, smo, ev_ref,
             jnp.float32(max_k - 1))
    _phase_b_compact(ev_ref, len_ref, sk0_ref, sk1_ref, ci_, di_, co, do_,
                     max_k)


def _fused_fpp(c: int, k: int) -> int:
    """Fused-kernel strip budget via the shared formula: 1-channel value
    stream (vs 6C rgba+depth), 2 extra per-pixel planes (length, ratio),
    and 7 per-slice record floats (5 scratch + the t0/t1 temporaries
    phase B broadcasts itself, as the compact seg kernel's)."""
    return strip_fpp(c, k, small_rows=_NSMALL, count_plane=False,
                     per_slice_records=7, stream_per_slice=1,
                     extra_planes=2)


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
    color, depth, small = packed
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

    wb = _pick_block_w(w, 4 * TILE_H * _fused_fpp(c, kk))
    grid = (h // TILE_H, pl.cdiv(w, wb))
    row = lambda *lead: pl.BlockSpec(lead + (TILE_H, wb),
                                     lambda j, i: (0,) * len(lead) + (j, i))
    state_specs = [row(kk, 4), row(kk, 2), row(_NSMALL)]
    sk_spec = pl.BlockSpec((c, 1, 1), lambda j, i: (0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_fused_kernel, max_k=max_k, tfc=tfc),
        grid=grid,
        in_specs=[row(c), row(), row(), row(), sk_spec, sk_spec]
        + state_specs,
        out_specs=state_specs,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in packed],
        scratch_shapes=[pltpu.VMEM((c, 5, TILE_H, wb), jnp.float32)],
        input_output_aliases={6: 0, 7: 1, 8: 2},
        interpret=interpret,
        name="sitpu_fold_fused",
    )(val, length, ratio, threshold, sk0, sk1, *packed)
    return tuple(out)


# ------------------------------------------- whole-march stream-fold kernel


def _fused_stream_kernel(val_ref, len_ref, ratio_ref, thr_ref, sk0_ref,
                         sk1_ref, ci_, di_, smi_, co, do_, smo, ev_ref, *,
                         max_k: int, tfc: tuple):
    """The fused shade+fold kernel over a WHOLE-march grid: the chunk
    loop is the innermost grid dimension and every state block's index
    map ignores it, so Mosaic keeps the [K,...] state resident in VMEM
    across all chunks of a pixel strip and writes it back ONCE — the
    state's HBM traffic drops from (2 x per chunk) to (1 x per march),
    the last memory term the per-chunk kernels still paid. The val
    stream must pre-exist in HBM (f32[S,H,W], built by the march's
    matmul phase), which the 1-channel fused feed makes affordable.
    Phase logic is identical to `_fused_kernel`; cross-chunk
    continuation works exactly as between per-chunk calls because phase
    B merges into the (now VMEM-resident) state after every chunk.

    Accumulation reads/writes the OUTPUT refs (initialized from the
    aliased inputs at the strip's first chunk): a revisited block only
    persists on the output side — re-reading the input refs after
    chunk 0 would see the strip's INITIAL state, not the accumulated
    one (the standard Pallas grid-accumulator pattern)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        co[...] = ci_[...]
        do_[...] = di_[...]
        smo[...] = smi_[...]

    _fused_kernel(val_ref, len_ref, ratio_ref, thr_ref, sk0_ref, sk1_ref,
                  co, do_, smo, co, do_, smo, ev_ref,
                  max_k=max_k, tfc=tfc)


def fused_stream_fold(packed, val: jnp.ndarray, length: jnp.ndarray,
                      ratio: jnp.ndarray, sk0: jnp.ndarray,
                      sk1: jnp.ndarray, threshold: jnp.ndarray, *,
                      max_k: int, chunk: int, tf,
                      interpret: Optional[bool] = None):
    """Fold an ENTIRE pre-materialized value stream in one pallas_call.

    val f32[S,H,W] (S a multiple of ``chunk``; -1 sentinel for dead
    samples); sk0/sk1 f32[S] per-slice depth ratios; length/ratio/
    threshold f32[H,W]. ``packed`` = `init_seg_packed` triple. The fold
    state crosses HBM once per strip instead of once per chunk."""
    if interpret is None:
        interpret = should_interpret()
    tfc = _tf_consts(tf)
    color, depth, small = packed
    kk = color.shape[0]
    _, _, h, w = color.shape
    s_total = val.shape[0]
    c = chunk
    if s_total % c:
        raise ValueError(f"stream length {s_total} not a multiple of "
                         f"chunk {c}")
    if h % TILE_H:
        raise ValueError(f"height {h} not a multiple of {TILE_H}")
    threshold = jnp.broadcast_to(jnp.asarray(threshold, jnp.float32), (h, w))
    length = jnp.broadcast_to(jnp.asarray(length, jnp.float32), (h, w))
    ratio = jnp.broadcast_to(jnp.asarray(ratio, jnp.float32), (h, w))
    sk0 = jnp.asarray(sk0, jnp.float32).reshape(s_total, 1, 1)
    sk1 = jnp.asarray(sk1, jnp.float32).reshape(s_total, 1, 1)

    wb = _pick_block_w(w, 4 * TILE_H * _fused_fpp(c, kk))
    nchunks = s_total // c
    # chunk dim INNERMOST (fastest): for each strip, all chunks run
    # consecutively and the constant-index state blocks are revisited
    grid = (h // TILE_H, pl.cdiv(w, wb), nchunks)
    row = lambda *lead: pl.BlockSpec(
        lead + (TILE_H, wb), lambda j, i, ci: (0,) * len(lead) + (j, i))
    stream = pl.BlockSpec((c, TILE_H, wb), lambda j, i, ci: (ci, j, i))
    sk_spec = pl.BlockSpec((c, 1, 1), lambda j, i, ci: (ci, 0, 0))
    state_specs = [row(kk, 4), row(kk, 2), row(_NSMALL)]
    out = pl.pallas_call(
        functools.partial(_fused_stream_kernel, max_k=max_k, tfc=tfc),
        grid=grid,
        in_specs=[stream, row(), row(), row(), sk_spec, sk_spec]
        + state_specs,
        out_specs=state_specs,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in packed],
        scratch_shapes=[pltpu.VMEM((c, 5, TILE_H, wb), jnp.float32)],
        input_output_aliases={6: 0, 7: 1, 8: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="sitpu_fold_fused_stream",
    )(val, length, ratio, threshold, sk0, sk1, *packed)
    return tuple(out)
