"""Shared bits of the Pallas TPU kernels (composite + march folds)."""

from __future__ import annotations

import jax

# f32 native tile: 8 sublanes x 128 lanes
TILE_H = 8
TILE_W = 128


def should_interpret() -> bool:
    """Run kernels in interpret mode off-TPU (tests, the virtual mesh)."""
    return jax.default_backend() != "tpu"
