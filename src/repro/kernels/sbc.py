"""Sparse-binary-compression kernels (TPU Pallas): the paper's uplink
compression hot-spot (Step 2, [24]) as a two-kernel pipeline.

  * ``sbc_stats``   — tiled reduction: per-lane partial sums/counts of
    positive/negative magnitudes above a threshold, accumulated across
    row blocks in the resident output tile.
  * ``sbc_apply``   — tiled map: binarize survivors to ±mean-magnitude.

Both kernels take the tensor as a lane-dense ``(rows, 128)`` slab and
their scalars as ``(·, 128)`` rows broadcast across lanes, so every block
is either ``(block_rows, 128)`` with ``block_rows % 8 == 0`` or a whole
small operand — the TPU tiling rule holds under any number of leading
``vmap`` axes (the engine vmaps them over clients and scenario rows), and
no scalar is ever stored to VMEM.

The global top-k threshold itself stays in XLA (jax.lax.top_k): a sort is
not a Pallas-shaped problem on TPU — the *bandwidth-bound streaming passes*
are, which is exactly what these kernels tile.  Composition + oracle:
kernels/ops.py vs compression.sbc.sbc_tensor.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# sublane rows of the stats tile: [pos_sum, neg_sum, pos_cnt, neg_cnt, 0…]
STAT_ROWS = 8


def _stats_kernel(x_ref, thr_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)            # (block_rows, 128)
    mag = jnp.abs(x)
    keep = mag >= thr_ref[...]                    # (1, 128) broadcast
    pos = keep & (x > 0)
    neg = keep & (x < 0)
    parts = (jnp.sum(jnp.where(pos, mag, 0.0), axis=0, keepdims=True),
             jnp.sum(jnp.where(neg, mag, 0.0), axis=0, keepdims=True),
             jnp.sum(pos.astype(jnp.float32), axis=0, keepdims=True),
             jnp.sum(neg.astype(jnp.float32), axis=0, keepdims=True))
    row = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
    tile = jnp.zeros(o_ref.shape, jnp.float32)
    for i, part in enumerate(parts):
        tile = jnp.where(row == i, part, tile)
    o_ref[...] += tile


def sbc_stats(x2d, thr_row, *, block_rows: int = 512,
              interpret: bool = False):
    """x2d: (rows, 128) with ``rows % block_rows == 0``; thr_row: (1, 128)
    threshold broadcast across lanes.  Returns the (8, 128) per-lane
    partials; rows 0-3 summed over lanes are [pos_sum, neg_sum, pos_cnt,
    neg_cnt]."""
    rows, lanes = x2d.shape
    if rows % block_rows:
        raise ValueError(f"{block_rows=} must divide {rows=}")
    return pl.pallas_call(
        _stats_kernel,
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, lanes), lambda b: (b, 0)),
                  pl.BlockSpec((1, lanes), lambda b: (0, 0))],
        out_specs=pl.BlockSpec((STAT_ROWS, lanes), lambda b: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((STAT_ROWS, lanes), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="sbc_stats",
    )(x2d, thr_row)


def _apply_kernel(x_ref, sc_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    sc = sc_ref[...]                              # (3, 128)
    thr, val_pos, val_neg = sc[0:1], sc[1:2], sc[2:3]
    keep = jnp.abs(x) >= thr
    out = jnp.where(keep & (x > 0), val_pos,
                    jnp.where(keep & (x < 0), val_neg, 0.0))
    o_ref[...] = out.astype(o_ref.dtype)


def sbc_apply(x2d, scalar_rows, *, block_rows: int = 512,
              interpret: bool = False):
    """scalar_rows: (3, 128) rows [thr, val_pos, val_neg], each broadcast
    across lanes (the value of the dropped sign group is 0)."""
    rows, lanes = x2d.shape
    if rows % block_rows:
        raise ValueError(f"{block_rows=} must divide {rows=}")
    return pl.pallas_call(
        _apply_kernel,
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, lanes), lambda b: (b, 0)),
                  pl.BlockSpec((3, lanes), lambda b: (0, 0))],
        out_specs=pl.BlockSpec((block_rows, lanes), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), x2d.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="sbc_apply",
    )(x2d, scalar_rows)
