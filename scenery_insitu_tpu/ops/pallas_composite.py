"""Pallas TPU kernel for the sort-last composite merge
(≅ VDICompositor.comp's per-pixel k-way merge + re-segmentation,
VDICompositor.comp:58-91,209-459).

The XLA path (ops.composite.composite_vdis) runs the supersegment state
machine as a ``lax.scan`` over the N*K depth-sorted slots with full-frame
[H, W] state — every scan iteration round-trips the state through HBM, and
with ``CompositeConfig.adaptive`` the threshold binary search multiplies
that by ``adaptive_iters`` more counting scans. This kernel fuses the
WHOLE composite — the adaptive search's counting passes AND the write pass
— over a (8, 128)-pixel tile held in VMEM: the slab stream is read from
HBM exactly once per tile, every counting/write iteration runs on
VMEM-resident state, and nothing intermediate ever spills.

The kernel body calls the very same ``supersegments.push``/``push_count``/
``finalize``/``adaptive_threshold``-equivalent logic the XLA path uses —
one implementation of the merge semantics, two schedules — so the parity
test (tests/test_pallas.py) can assert exact equality.

On CPU (tests, the 8-device virtual mesh) the kernel runs in interpret
mode automatically; on TPU it compiles with Mosaic.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from scenery_insitu_tpu.ops import supersegments as ss
from scenery_insitu_tpu.ops.pallas_util import TILE_H, TILE_W, should_interpret


def _kernel(sc_ref, sd_ref, thr_ref, color_ref, depth_ref,
            seg_ref, ends_ref, prev_ref, flags_ref, k_ref,
            *, k_out: int, gap_eps: float, adaptive_iters: int,
            thr_max: float):
    # State lives in VMEM scratch, not in the fori_loop carry: Mosaic cannot
    # legalize an scf.for with dozens of carried vectors (one per [th, tw]
    # plane of SegState), and bool carries are illegal outright. The loop
    # carries nothing; each iteration loads state from the scratch refs,
    # runs the shared supersegments fold, and stores it back.
    nk = sc_ref.shape[0]
    th, tw = thr_ref.shape

    # ------------------------------------------- adaptive threshold search
    # (≅ ss.adaptive_threshold, but the counting marches run on the VMEM-
    # resident slab tile instead of re-scanning HBM adaptive_iters times)
    if adaptive_iters > 0:
        def count_pass(mid):
            # CountState in scratch: k_ref=count, prev_ref=prev_rgb,
            # flags_ref[1]=prev_empty, ends_ref[0]=prev_end
            k_ref[...] = jnp.zeros((th, tw), jnp.int32)
            prev_ref[...] = jnp.zeros((3, th, tw), jnp.float32)
            flags_ref[1] = jnp.ones((th, tw), jnp.float32)
            ends_ref[0] = jnp.full((th, tw), -jnp.inf, jnp.float32)

            def body(i, _):
                st = ss.CountState(count=k_ref[...], prev_rgb=prev_ref[...],
                                   prev_empty=flags_ref[1] > 0.5,
                                   prev_end=ends_ref[0])
                st = ss.push_count(st, mid, sc_ref[i], sd_ref[i, 0],
                                   sd_ref[i, 1], gap_eps)
                k_ref[...] = st.count
                prev_ref[...] = st.prev_rgb
                flags_ref[1] = st.prev_empty.astype(jnp.float32)
                ends_ref[0] = st.prev_end
                return 0

            jax.lax.fori_loop(0, nk, body, 0)
            return k_ref[...]

        lo = jnp.zeros((th, tw), jnp.float32)
        hi = jnp.full((th, tw), thr_max, jnp.float32)
        for _ in range(adaptive_iters):
            mid = 0.5 * (lo + hi)
            too_many = count_pass(mid) > k_out
            lo = jnp.where(too_many, mid, lo)
            hi = jnp.where(too_many, hi, mid)
        thr = hi
    else:
        thr = thr_ref[...]

    # ---------------------------------------------------------- write pass
    color_ref[...] = jnp.zeros_like(color_ref)
    depth_ref[...] = jnp.full_like(depth_ref, jnp.inf)
    seg_ref[...] = jnp.zeros_like(seg_ref)
    ends_ref[...] = jnp.zeros_like(ends_ref)
    prev_ref[...] = jnp.zeros_like(prev_ref)
    flags_ref[...] = jnp.stack([jnp.zeros((th, tw), jnp.float32),
                                jnp.ones((th, tw), jnp.float32)])
    k_ref[...] = jnp.zeros((th, tw), jnp.int32)

    def load_state() -> ss.SegState:
        return ss.SegState(
            out_color=color_ref[...],
            out_start=depth_ref[:, 0],
            out_end=depth_ref[:, 1],
            k=k_ref[...],
            open_=flags_ref[0] > 0.5,
            seg_rgba=seg_ref[...],
            seg_start=ends_ref[0],
            seg_end=ends_ref[1],
            prev_rgb=prev_ref[...],
            prev_empty=flags_ref[1] > 0.5,
        )

    def store_state(st: ss.SegState) -> None:
        color_ref[...] = st.out_color
        depth_ref[:, 0] = st.out_start
        depth_ref[:, 1] = st.out_end
        k_ref[...] = st.k
        flags_ref[0] = st.open_.astype(jnp.float32)
        flags_ref[1] = st.prev_empty.astype(jnp.float32)
        seg_ref[...] = st.seg_rgba
        ends_ref[0] = st.seg_start
        ends_ref[1] = st.seg_end
        prev_ref[...] = st.prev_rgb

    def body(i, _):
        st = ss.push(load_state(), k_out, thr, sc_ref[i],
                     sd_ref[i, 0], sd_ref[i, 1], gap_eps)
        store_state(st)
        return 0

    jax.lax.fori_loop(0, nk, body, 0)
    color, depth = ss.finalize(load_state())
    color_ref[...] = color
    depth_ref[...] = depth


def resegment_sorted(sc: jnp.ndarray, sd: jnp.ndarray,
                     threshold: Optional[jnp.ndarray], k_out: int,
                     gap_eps: float = 1e-4,
                     interpret: Optional[bool] = None,
                     adaptive_iters: int = 0, thr_max: float = 2.0
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fold a depth-sorted slab stream into K_out supersegments per pixel.

    sc f32[NK, 4, H, W] premultiplied (empty slots alpha 0),
    sd f32[NK, 2, H, W] (start, end; +inf when empty).
    ``adaptive_iters > 0`` runs the per-pixel threshold binary search
    inside the kernel (``threshold`` may be None); otherwise ``threshold``
    f32[H, W] is used as-is. Returns (color f32[K_out, 4, H, W], depth
    f32[K_out, 2, H, W]) — exactly what the XLA scans in composite_vdis
    produce.
    """
    nk, _, h, w = sc.shape
    if interpret is None:
        interpret = should_interpret()
    if threshold is None:
        threshold = jnp.zeros((h, w), jnp.float32)

    # pad pixels to tile multiples; padded pixels see only empty slabs
    ph = (-h) % TILE_H
    pw = (-w) % TILE_W
    if ph or pw:
        pad = ((0, 0), (0, 0), (0, ph), (0, pw))
        sc = jnp.pad(sc, pad)
        sd = jnp.pad(sd, pad, constant_values=jnp.inf)
        threshold = jnp.pad(threshold, ((0, ph), (0, pw)))
    hp, wp = h + ph, w + pw
    grid = (hp // TILE_H, wp // TILE_W)

    kernel = functools.partial(_kernel, k_out=k_out, gap_eps=gap_eps,
                               adaptive_iters=adaptive_iters,
                               thr_max=thr_max)
    color, depth = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((nk, 4, TILE_H, TILE_W), lambda i, j: (0, 0, i, j)),
            pl.BlockSpec((nk, 2, TILE_H, TILE_W), lambda i, j: (0, 0, i, j)),
            pl.BlockSpec((TILE_H, TILE_W), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((k_out, 4, TILE_H, TILE_W),
                         lambda i, j: (0, 0, i, j)),
            pl.BlockSpec((k_out, 2, TILE_H, TILE_W),
                         lambda i, j: (0, 0, i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k_out, 4, hp, wp), jnp.float32),
            jax.ShapeDtypeStruct((k_out, 2, hp, wp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((4, TILE_H, TILE_W), jnp.float32),   # open seg rgba
            pltpu.VMEM((2, TILE_H, TILE_W), jnp.float32),   # seg start/end
            pltpu.VMEM((3, TILE_H, TILE_W), jnp.float32),   # prev rgb
            pltpu.VMEM((2, TILE_H, TILE_W), jnp.float32),   # open/prev_empty
            pltpu.VMEM((TILE_H, TILE_W), jnp.int32),        # next free slot
        ],
        interpret=interpret,
        name="sitpu_resegment_sorted",
    )(sc, sd, threshold)

    if ph or pw:
        color = color[:, :, :h, :w]
        depth = depth[:, :, :h, :w]
    return color, depth
