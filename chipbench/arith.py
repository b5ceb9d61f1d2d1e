"""Shape-only arithmetic: the bytes and operations the algorithm needs for
one frame, from the configuration's sizes alone. Kept with the benchmark so
that a kernel's schedule cannot move its own yardstick."""

import json
import math
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> dict:
    """Published peaks of this device kind; a kind that is not in
    peaks.json is an error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["kinds"]
    kind = (device_kind or "").lower()
    for key, peaks in table.items():
        if key in kind:
            return peaks
    raise KeyError(f"device kind {device_kind!r} is not in chipbench/"
                   "peaks.json; add its published peaks with their source")


def intermediate_grid(shape: dict) -> tuple:
    """(ni, nj) of the slice march's intermediate image for a march along
    z: the in-plane extents times `slicer_scale`, rounded up to a multiple
    of lcm(8, ranks) (the column exchange needs ni divisible by ranks)."""
    d, h, w = shape["grid"]
    ranks = shape["ranks"]
    step = 8 * ranks // math.gcd(8, ranks)
    rnd = lambda n: max(step, -(-int(n * shape["slicer_scale"]) // step)
                        * step)
    return rnd(w), rnd(h)


def sim_floor_bytes_per_frame(shape: dict) -> float:
    """The least HBM traffic of one frame's sim advance: u and v, f32, read
    once and written once PER FRAME, however many steps the frame takes.
    A fully fused advance moves this much; every schedule today moves
    more, so the share built on it measures the distance from that."""
    d, h, w = shape["grid"]
    return 2 * 2 * 4.0 * d * h * w


def march_dense_flops_per_frame(shape: dict) -> float:
    """FLOPs of the two banded resampling matmuls of every slice, dense:
    [nj, H] @ [H, W] and [nj, W] @ [W, ni] for each of D slices (march
    along z). Skipping levers (occupancy, LOD) execute fewer."""
    d, h, w = shape["grid"]
    ni, nj = intermediate_grid(shape)
    return d * (2.0 * nj * h * w + 2.0 * nj * w * ni)


def vdi_bytes_per_frame(shape: dict) -> int:
    """Bytes of one delivered VDI: K slots of f32 RGBA + two f32 depths
    (24 B) on the intermediate grid."""
    ni, nj = intermediate_grid(shape)
    return shape["k"] * shape["bytes_per_slot"] * ni * nj
