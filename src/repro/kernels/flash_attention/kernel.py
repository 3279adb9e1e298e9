"""Flash attention as a Pallas TPU kernel.

Online-softmax blocked attention (Dao et al., adapted to the TPU memory
hierarchy): the grid walks (batch, q-head, q-block) in parallel and the
k-block dimension sequentially ("arbitrary"), carrying the running max
``m``, normalizer ``l``, and accumulator in VMEM scratch.  Block shapes
are MXU-aligned (q/k blocks multiples of 128 lanes, head_dim untiled) and
sized so the working set — one q tile, one k tile, one v tile, and the
f32 accumulator — stays a few MB of VMEM.

Causality and sliding windows are handled two ways:
* whole out-of-range k-blocks are skipped with ``pl.when`` (no MXU work),
* partially masked blocks apply the positional mask to the logits.

GQA: q-head h reads kv-head ``h * KVH // H`` via the k/v index_maps.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # [1, 1, bq, hd]
    k_ref,  # [1, 1, bk, hd]
    v_ref,  # [1, 1, bk, hd]
    o_ref,  # [1, 1, bq, hd]
    m_scr,  # [bq, 1] f32
    l_scr,  # [bq, 1] f32
    acc_scr,  # [bq, hd] f32
    *,
    causal: bool,
    window: Optional[int],
    logit_softcap: Optional[float],
    block_q: int,
    block_k: int,
    num_k_blocks: int,
    sm_scale: float,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # block-level reachability: any (q, k) pair in range?
    in_range = True
    if causal:
        in_range = jnp.logical_and(in_range, k_start <= q_start + block_q - 1)
    if window is not None:
        in_range = jnp.logical_and(
            in_range, k_start + block_k - 1 > q_start - window
        )

    @pl.when(in_range)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [bq, bk]
        if logit_softcap is not None:
            logits = logit_softcap * jnp.tanh(logits / logit_softcap)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev = m_scr[...]  # [bq, 1]
        m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
        p = jnp.exp(logits - m_new)  # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)  # [bq, 1]
        l_new = alpha * l_scr[...] + p.sum(axis=1, keepdims=True)
        acc = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jnp.ndarray,  # [B, H, Sq, hd]
    k: jnp.ndarray,  # [B, KVH, Sk, hd]
    v: jnp.ndarray,  # [B, KVH, Sk, hd]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    b, h, sq, hd = q.shape
    _, kvh, sk, _ = k.shape
    assert h % kvh == 0, (h, kvh)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, block_q, sk, block_k)
    nq, nk = sq // block_q, sk // block_k
    group = h // kvh

    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        window=window,
        logit_softcap=logit_softcap,
        block_q=block_q,
        block_k=block_k,
        num_k_blocks=nk,
        sm_scale=hd ** -0.5,
    )
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b_, h_, qi, ki: (b_, h_ // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b_, h_, qi, ki: (b_, h_ // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
