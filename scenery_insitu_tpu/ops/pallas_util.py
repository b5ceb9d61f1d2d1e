"""Shared bits of the Pallas TPU kernels (composite + march folds): the
native tile, interpret mode off a TPU, and the pixel-strip sizing every
fold kernel of ops/pallas_seg.py tiles by.

Strip tiling: (8, WB) strips — 8 sublanes x a width block, grid over
(H/8, ceil(W/WB)). WB is the full row when the strip's VMEM estimate fits
the scoped budget and otherwise the largest multiple of 128 that does: at
the 512^3 scale (W=640, K=C=16) a full-width strip of the first fold
kernel demanded 16.39 MB scoped VMEM against Mosaic's 16 MB limit, and a
standalone compile probe passes where the same kernel embedded in the
frame's while/cond fails on the extra stack frames, so the geometry must
leave headroom rather than ride the limit. W needn't be a multiple of the
block: the last block's lane padding is masked by Mosaic and no HBM copy
is spent on alignment. H must be a multiple of 8 (`slicer.make_spec`
guarantees it).
"""

from __future__ import annotations

from typing import Optional

import jax

# f32 native tile: 8 sublanes x 128 lanes
TILE_H = 8
TILE_W = 128

# VMEM budget the strip ESTIMATE must fit in. The estimate is deliberately
# conservative — ~1.65x the 16.39 MB Mosaic measured for a K=16/C=16
# 640-wide strip fed six planes a slice (scoped-vmem error, window 2) — so
# 14 MB of estimate is ~8.5 MB of true usage: ample headroom under the
# 16 MB scoped limit for Mosaic's stack frames when the kernel sits inside
# lax control flow (the 512^3 OOM rode the limit and lost by 404 KB).
_VMEM_STRIP_BUDGET = 14 * 1024 * 1024
# geometry override for tests of the width-tiled grid (no test-sized
# frame exceeds the budget); None = budget-driven choice
_FORCE_BLOCK_W: Optional[int] = None
# the strip estimate treats K as at least this value, so the block width
# is the same for every K <= _EST_K; larger K shrinks the block.
_EST_K = 32


def should_interpret() -> bool:
    """Run kernels in interpret mode off-TPU (tests, the virtual mesh)."""
    return jax.default_backend() != "tpu"


def strip_fpp(c: int, k: int, *, small_rows: int, per_slice_records: int,
              stream_per_slice: int, extra_planes: int) -> int:
    """Strip VMEM estimate in floats per pixel column — THE one budget
    formula the write-fold kernels share: in+out blocks double-buffered
    (x2x2) over (stream_per_slice*C stream + 1 threshold + extra
    per-pixel planes + 6K state + small rows), plus the per-slice
    record arrays and slack for phase temporaries.
    K floored at _EST_K."""
    return (2 * 2 * (stream_per_slice * c + 1 + extra_planes
                     + 6 * max(k, _EST_K) + small_rows)
            + per_slice_records * c + 64)


def pick_block_w(w: int, bytes_per_col: int) -> int:
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
            "ops.fold.block_width", "budgeted strip",
            "128-lane floor",
            f"strip needs {bytes_per_col * 128 / 2**20:.1f} MB VMEM at "
            "the 128-lane minimum block width — over the "
            f"{_VMEM_STRIP_BUDGET / 2**20:.0f} MB budget; compiling at "
            "the floor anyway (Mosaic may refuse it)", stacklevel=3)
    return max(128, min(wb, w))
