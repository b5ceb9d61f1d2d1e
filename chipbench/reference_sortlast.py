"""Sort-last compositing written down plainly, for the multi-rank
configurations. Imports nothing of the program, like `reference.py`.

Every rank renders its own part of the volume into a VDI of K
supersegments per pixel. What the viewer must see is all R*K of them, per
pixel in front-to-back order, composited "over". The program gets there by
a column exchange, a merge network and a resegmenting pass that leaves K
slots; this file gets there by sorting and compositing, nothing else.
"Over" is associative and resegmenting only joins slabs that are adjacent
in depth order, so `reference.decode` of the program's K-slot VDI must
agree with `sortlast_decode` of the fragments it was made from, up to the
rounding of a float32 sum taken in another order.
"""

import numpy as np


def sortlast_decode(colors: np.ndarray, depths: np.ndarray) -> np.ndarray:
    """The image R ranks' fragments decode to from their own view.

    `colors` f32[R, K, 4, H, W] premultiplied RGBA, `depths` f32[R, K, 2, H,
    W] front and back depth of each supersegment; a slot whose front depth
    is not finite is empty, whatever its colour holds. Per pixel all R*K
    slots are ordered by front depth (ties: by rank, then slot) and
    composited front to back in float32. f32[4, H, W], on the host."""
    r, k = colors.shape[:2]
    c = np.asarray(colors, np.float32).reshape((r * k,) + colors.shape[2:])
    d = np.asarray(depths, np.float32).reshape((r * k,) + depths.shape[2:])
    front = d[:, 0]
    c = np.where(np.isfinite(front)[:, None], c, np.float32(0.0))
    order = np.argsort(front, axis=0, kind="stable")            # [R*K, H, W]
    c = np.take_along_axis(c, order[:, None], axis=0)
    acc = np.zeros(c.shape[1:], np.float32)
    for slot in c:
        acc += (np.float32(1.0) - acc[3:4]) * slot
    return acc
