"""Per-block int8 absmax quantization as a Pallas TPU kernel.

The compute hot-spot introduced by the paper's setting: gradients must be
compressed *at line rate* before the inter-data-center hop (hier_int8
sync), i.e. the quantizer must stream the full gradient through the VPU
faster than the WAN drains it.  The grid walks ``row_tile``-row slabs of
a [rows, lanes] array in lane groups of at most 128 wire-format blocks
(one f32 scale per 256 int8 payload).  A row no wider than 128 blocks is
one group, whose scale block ``(row_tile, lanes/256)`` spans the full
scale width; a wider row (a vocabulary-wide unembedding) is walked in
groups of exactly 128 blocks, whose scale block is 128 lanes wide.  Both
are legal TPU blocks, and a group bounds the slab in VMEM and the
unrolled loop at 128 blocks.  The last group may overhang the row: its
blocks past the end are whole 256-lane blocks, so they read padding and
their writes are dropped, and no real block is affected.  Inside a slab
the blocks are static, 128-aligned lane slices, so absmax reduction and
scaling vectorize with no cross-lane shuffles.  Row tiles are multiples
of 32 (the int8 sublane tiling) unless one tile covers every row.
Quantize and dequantize are separate kernels (they run on opposite sides
of the WAN).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 256  # lanes per scale block (wire format)
ROW_TILE = 256  # rows per grid step
ROW_ALIGN = 32  # int8 sublane tiling: a partial row tile is a multiple of this
GROUP = 128  # scale blocks per lane group: one 128-lane tile of scales


def lane_group(nblocks: int) -> int:
    """Scale blocks per grid step along a row of ``nblocks`` blocks."""
    return min(nblocks, GROUP)


def _checked_row_tile(rows: int, row_tile: int) -> int:
    """The row tile the kernels use for ``rows``; raises if the TPU cannot tile it."""
    rt = min(row_tile, rows)
    if rows % rt or (rt != rows and rt % ROW_ALIGN):
        raise ValueError(
            f"row tile {rt} cannot tile {rows} rows: it must divide them and be "
            f"a multiple of {ROW_ALIGN} (pad rows with repro.kernels.wan_quant.ops)"
        )
    return rt


def _quant_kernel(x_ref, q_ref, s_ref, *, nblocks: int):
    col = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, 1)
    scales = jnp.zeros(s_ref.shape, jnp.float32)
    for c in range(nblocks):
        lanes = slice(c * BLOCK, (c + 1) * BLOCK)
        x = x_ref[:, lanes].astype(jnp.float32)  # [rt, BLOCK]
        absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)  # [rt, 1]
        scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
        q_ref[:, lanes] = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        scales = jnp.where(col == c, scale, scales)
    s_ref[...] = scales


def _dequant_kernel(q_ref, s_ref, x_ref, *, nblocks: int):
    s = s_ref[...]  # [rt, nblocks]
    for c in range(nblocks):
        lanes = slice(c * BLOCK, (c + 1) * BLOCK)
        x_ref[:, lanes] = q_ref[:, lanes].astype(jnp.float32) * s[:, c : c + 1]


def wan_quant(
    x: jnp.ndarray, *, row_tile: int = ROW_TILE, interpret: bool = False
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [rows, lanes] -> (int8 [rows, lanes], scales f32 [rows, lanes/256])."""
    rows, lanes = x.shape
    if lanes % BLOCK:
        raise ValueError(f"lanes {lanes} is not a multiple of {BLOCK}")
    rt = _checked_row_tile(rows, row_tile)
    nblocks = lanes // BLOCK
    g = lane_group(nblocks)
    q, s = pl.pallas_call(
        functools.partial(_quant_kernel, nblocks=g),
        grid=(rows // rt, pl.cdiv(nblocks, g)),
        in_specs=[pl.BlockSpec((rt, g * BLOCK), lambda r, c: (r, c))],
        out_specs=[
            pl.BlockSpec((rt, g * BLOCK), lambda r, c: (r, c)),
            pl.BlockSpec((rt, g), lambda r, c: (r, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, lanes), jnp.int8),
            jax.ShapeDtypeStruct((rows, nblocks), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="wan_quant",
    )(x)
    return q, s


def wan_dequant(
    q: jnp.ndarray, scales: jnp.ndarray, *, row_tile: int = ROW_TILE,
    interpret: bool = False,
) -> jnp.ndarray:
    """(int8 [rows, lanes], scales f32 [rows, lanes/256]) -> f32 [rows, lanes]."""
    rows, lanes = q.shape
    if lanes % BLOCK:
        raise ValueError(f"lanes {lanes} is not a multiple of {BLOCK}")
    rt = _checked_row_tile(rows, row_tile)
    nblocks = lanes // BLOCK
    g = lane_group(nblocks)
    return pl.pallas_call(
        functools.partial(_dequant_kernel, nblocks=g),
        grid=(rows // rt, pl.cdiv(nblocks, g)),
        in_specs=[
            pl.BlockSpec((rt, g * BLOCK), lambda r, c: (r, c)),
            pl.BlockSpec((rt, g), lambda r, c: (r, c)),
        ],
        out_specs=pl.BlockSpec((rt, g * BLOCK), lambda r, c: (r, c)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="wan_dequant",
    )(q, scales)
