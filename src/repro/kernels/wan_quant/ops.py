"""Jit-ready wrappers for the WAN quantization kernels.

Handles arbitrary pytree-leaf shapes: pads the trailing dim to a 256
multiple, flattens leading dims to rows, pads rows to a legal row tile
(a multiple of 32, or one tile holding every row) and dispatches to the
compiled Pallas kernel.  Tests on the CPU pass ``interpret=True``.  The
round-trip composes with the error-feedback machinery in
``repro.distributed.compression``.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from .kernel import BLOCK, ROW_ALIGN, ROW_TILE, lane_group, wan_dequant, wan_quant

_SLAB_BYTES = 2 << 20  # f32 bytes of one slab, above the 32-row minimum


def _to_rows(x: jnp.ndarray) -> jnp.ndarray:
    """Any-shape leaf -> [rows, lanes] with lanes padded to a 256 multiple."""
    x = x.reshape(1, -1) if x.ndim < 2 else x.reshape(-1, x.shape[-1])
    pad = (-x.shape[-1]) % BLOCK
    return jnp.pad(x, ((0, 0), (0, pad))) if pad else x


def _row_tile(rows: int, lanes: int) -> int:
    """Largest legal row tile whose f32 slab (one lane group) fits ``_SLAB_BYTES``."""
    slab_lanes = lane_group(lanes // BLOCK) * BLOCK
    fit = min(ROW_TILE, _SLAB_BYTES // (4 * slab_lanes)) // ROW_ALIGN * ROW_ALIGN
    rt = max(fit, ROW_ALIGN)
    return rows if rows <= rt else rt


def _pad_rows(x: jnp.ndarray, rt: int) -> jnp.ndarray:
    pad = (-x.shape[0]) % rt
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize(x: jnp.ndarray, *, interpret: bool = False):
    """Any-shape leaf -> (int8 [rows, lanes], f32 scales [rows, lanes/256])."""
    rows = _to_rows(x.astype(jnp.float32))
    n, lanes = rows.shape
    rt = _row_tile(n, lanes)
    q, s = wan_quant(_pad_rows(rows, rt), row_tile=rt, interpret=interpret)
    return q[:n], s[:n]


@functools.partial(jax.jit, static_argnames=("orig_shape", "interpret"))
def dequantize(q, s, *, orig_shape: Tuple[int, ...], interpret: bool = False):
    """Inverse of :func:`quantize`: back to a float32 leaf of ``orig_shape``."""
    n, lanes = q.shape
    rt = _row_tile(n, lanes)
    full = wan_dequant(
        _pad_rows(q, rt), _pad_rows(s, rt), row_tile=rt, interpret=interpret
    )
    last = orig_shape[-1] if orig_shape else 1
    return full[:n, :last].reshape(orig_shape)
