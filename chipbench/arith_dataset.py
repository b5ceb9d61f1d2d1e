"""Shape-only arithmetic of the raw-dataset configurations, kept with the
benchmark beside `arith.py` (which an accepted benchmark's PR may not
edit): the bytes of the volume at the dtype its file has, and the least
HBM traffic of one frame's march + fold."""

import numpy as np

from chipbench import arith


def volume_bytes(shape: dict, dtype: str = "") -> int:
    """Bytes of the volume `shape.grid` at `dtype` (default: the file's
    own, `shape.dtype`): 833,617,920 for Kingsnake's 795 x 1024 x 1024
    u8, four times that widened to float32."""
    d, h, w = shape["grid"]
    return d * h * w * np.dtype(dtype or shape["dtype"]).itemsize


def march_floor_bytes_per_frame(shape: dict) -> int:
    """The least HBM traffic of one frame's march + fold: the volume read
    once at its native dtype and the VDI written once. Every schedule
    today moves more (the shaded chunks between march and fold, the
    fold's state per chunk); the share built on it measures the distance
    from that."""
    return volume_bytes(shape) + arith.vdi_bytes_per_frame(shape)
