# SITPU-PALLAS good fixture: the same kernel with a divisibility guard
# and a whole (unblocked) SMEM scalar output. Parsed by the linter only.
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_H = 8
TILE_W = 128


def _kernel(x_ref, o_ref, s_ref):
    o_ref[...] = x_ref[...] * 2.0
    s_ref[pl.program_id(0), 0] = jnp.max(x_ref[...])


def double_chunk(x):
    h, w = x.shape
    if h % TILE_H:
        raise ValueError(f"height {h} not a multiple of {TILE_H}")
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _kernel, grid=(h // TILE_H,),
        in_specs=[pl.BlockSpec((TILE_H, w), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((TILE_H, w), lambda i: (i, 0)), smem],
        out_shape=[jax.ShapeDtypeStruct((h, w), jnp.float32),
                   jax.ShapeDtypeStruct((h // TILE_H, 1), jnp.float32)],
    )(x)
