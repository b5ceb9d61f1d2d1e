# SITPU-PALLAS bad fixture: a kernel entry with no divisibility handling
# and a blocked SMEM scalar output. Parsed by the linter only — never
# imported or executed.
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_H = 8
TILE_W = 128


def _kernel(x_ref, o_ref, s_ref):
    o_ref[...] = x_ref[...] * 2.0
    s_ref[0, 0] = jnp.max(x_ref[...])


def double_chunk(x):
    # no % guard / padding: h not a multiple of TILE_H floors the grid
    h, w = x.shape
    # blocked SMEM scalar output: Mosaic refuses (1, 1) SMEM blocks
    smem = pl.BlockSpec((1, 1), lambda i: (i, 0),
                        memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _kernel, grid=(h // TILE_H,),
        in_specs=[pl.BlockSpec((TILE_H, w), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((TILE_H, w), lambda i: (i, 0)), smem],
        out_shape=[jax.ShapeDtypeStruct((h, w), jnp.float32),
                   jax.ShapeDtypeStruct((h // TILE_H, 1), jnp.float32)],
    )(x)
