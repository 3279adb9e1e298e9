"""RWKV6 WKV recurrence as a Pallas TPU kernel.

The sub-quadratic engine behind the `long_500k` shapes: per (batch, head)
the recurrence carries an [N, N] state (N = 64 -> 16 KB f32, comfortably
VMEM-resident) while streaming T timesteps through in chunks.

Layout is head-major, [B, H, T, N]: a grid step's block is one
(batch, head)'s ``(chunk, N)`` slab, whose last two dims are legal TPU
block dims (``chunk`` a multiple of 8, ``N`` the full head width).  The
model's [B, T, H, N] activations are transposed on the way in and out.

Grid: (B, H, T/chunk) with the time dimension sequential ("arbitrary") —
the state lives in VMEM scratch across chunk steps, so HBM traffic is
exactly one read of (r, k, v, w) and one write of the output: the kernel
is HBM-bandwidth-bound by construction, which is the roofline-optimal
shape for this memory-bound recurrence (arithmetic intensity ~N/2).

Inside a chunk a ``fori_loop`` walks 8-timestep slabs (the chip loads
sublanes only at 8-aligned dynamic offsets) and unrolls the slab's
rank-1 updates:
    out_t  = r_t . (S + u * k_t v_t^T) = r_t S + (r_t . (u * k_t)) v_t
    S     <- diag(w_t) S + k_t v_t^T
Every operand is a (1, N) row, a static slice of the slab.  The (N, 1)
columns the state update needs (k_t, w_t) are the row's diagonal
embedding summed over lanes, which keeps the body to broadcasts, lane
reductions and one (1, N) x (N, N) matmul.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8  # timesteps per aligned slab load (the f32 sublane tile)


def _wkv_kernel(
    r_ref,  # [1, 1, chunk, N]
    k_ref,
    v_ref,
    w_ref,
    u_ref,  # [1, 1, N]
    s0_ref,  # [1, 1, N, N]
    o_ref,  # [1, 1, chunk, N]
    sout_ref,  # [1, 1, N, N]
    state_scr,  # [N, N] f32 VMEM scratch
    *,
    chunk: int,
    num_chunks: int,
):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        state_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    n = state_scr.shape[0]
    eye = (
        jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    ).astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)  # [1, N]

    def group(g, state):
        # one aligned (ROWS, N) slab per operand; rows are static slices of it
        rows = pl.ds(pl.multiple_of(g * ROWS, ROWS), ROWS)
        r, k, v, w = (
            ref[0, 0, rows, :].astype(jnp.float32) for ref in (r_ref, k_ref, v_ref, w_ref)
        )
        row_id = jax.lax.broadcasted_iota(jnp.int32, r.shape, 0)
        out = jnp.zeros(r.shape, jnp.float32)
        for j in range(ROWS):
            r_t, k_t, v_t, w_t = (x[j : j + 1] for x in (r, k, v, w))  # [1, N]
            bonus = jnp.sum(r_t * u * k_t, axis=1, keepdims=True)  # [1, 1]
            out_t = jax.lax.dot_general(
                r_t, state, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) + bonus * v_t  # [1, N]
            out = jnp.where(row_id == j, out_t, out)
            k_col = jnp.sum(eye * k_t, axis=1, keepdims=True)  # [N, 1]
            w_col = jnp.sum(eye * w_t, axis=1, keepdims=True)
            state = state * w_col + k_col * v_t
        o_ref[0, 0, rows, :] = out.astype(o_ref.dtype)
        return state

    state = jax.lax.fori_loop(0, chunk // ROWS, group, state_scr[...])
    state_scr[...] = state

    @pl.when(ti == num_chunks - 1)
    def _final():
        sout_ref[0, 0] = state.astype(sout_ref.dtype)


def wkv6_fwd(
    r: jnp.ndarray,  # [B, T, H, N]
    k: jnp.ndarray,
    v: jnp.ndarray,
    w: jnp.ndarray,
    u: jnp.ndarray,  # [H, N]
    state0: jnp.ndarray,  # [B, H, N, N]
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (out [B, T, H, N] f32, final state [B, H, N, N] f32)."""
    b, t, h, n = r.shape
    chunk = min(chunk, t)
    if t % chunk or chunk % ROWS:
        raise ValueError(
            f"chunk {chunk} cannot tile T={t}: it must divide T and be a "
            f"multiple of {ROWS}"
        )
    nchunks = t // chunk

    kernel = functools.partial(_wkv_kernel, chunk=chunk, num_chunks=nchunks)
    seq_spec = pl.BlockSpec((1, 1, chunk, n), lambda b_, h_, ti: (b_, h_, ti, 0))
    state_spec = pl.BlockSpec((1, 1, n, n), lambda b_, h_, ti: (b_, h_, 0, 0))
    out, sout = pl.pallas_call(
        kernel,
        grid=(b, h, nchunks),
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((1, 1, n), lambda b_, h_, ti: (h_, 0, 0)),
            state_spec,
        ],
        out_specs=[seq_spec, state_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, n), jnp.float32),
            jax.ShapeDtypeStruct((b, h, n, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="wkv6_fwd",
    )(*(jnp.swapaxes(x, 1, 2) for x in (r, k, v, w)), u.reshape(h, 1, n), state0)
    return jnp.swapaxes(out, 1, 2), sout
