"""Shape-only arithmetic of a raw-dataset configuration whose file is wider
than a byte (`beechnut-u16-1chip`), beside `arith.py` and
`arith_dataset.py` (an accepted benchmark's files): the matmul passes the
march EXECUTES for such a field, and their FLOPs.

A u8, bf16 or f32 chunk is one operand of each of the march's two
resampling contractions (`arith.march_dense_flops_per_frame`: one pass
each). A u16 chunk meets the first weight matrix as its two byte planes
(two passes, recombined on the f32 accumulator) and the recombined f32
intermediate meets the second as two bfloat16 terms (two passes):
`scenery_insitu_tpu/ops/slicer.resample_wide`."""

from chipbench import arith


def operand_passes(planes: float) -> tuple:
    """(passes of the first contraction, passes of the second) of a march
    that resamples a chunk as `planes` operand planes (the program's
    counter `march_operand_planes` per frame): the byte planes into the
    first, and as many bfloat16 terms of the recombined f32 intermediate
    into the second (8 bits of mantissa a term: 16 bits take two).
    (1, 1) for a chunk that is itself the operand."""
    return (planes, planes)


def march_executed_flops_per_frame(shape: dict, planes: float) -> float:
    """FLOPs of the resampling matmuls as executed, dense: per slice
    [nj, H] @ [H, W] once per byte plane and [nj, W] @ [W, ni] once per
    term of the intermediate (march along z). 2 x
    `arith.march_dense_flops_per_frame` for two planes: 18.675e12 a frame
    at 1546 x 1024 x 1024 on a 1280 x 1280 grid. Skipping levers
    (occupancy) execute fewer."""
    d, h, w = shape["grid"]
    ni, nj = arith.intermediate_grid(shape)
    first, second = operand_passes(planes)
    return d * (first * 2.0 * nj * h * w + second * 2.0 * nj * w * ni)
