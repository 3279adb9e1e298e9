"""Grouped-query attention with full/sliding-window masks and KV caching.

How attention is computed follows ``cfg.attn_impl``:

* ``"auto"`` (the default).  :func:`attention_forward` is causal
  self-attention over positions 0..S-1 by construction: training and
  prefill call it on a whole sequence.  Compiled for a TPU, with S a
  multiple of 128, it runs JAX's Pallas splash-attention kernel, forward
  and backward (:func:`_sdpa_flash`); the kernel computes sliding windows
  (``LocalMask``) and logit softcaps itself.  On any other platform, or
  for any other S, it runs the jnp form that :func:`sdpa` picks: the
  KV-block scan when S >= 2 * ``attn_block`` and divides into blocks, the
  dense form otherwise.  Decode (one query against the cache) always runs
  :func:`sdpa`.
* ``"naive"``, ``"chunked"``, ``"chunked_kv"``: that jnp form everywhere.

The jnp forms are the numerical reference; the kernel is tested against
them in Pallas interpret mode.  ``jax.lax.platform_dependent`` makes the
choice when the program is lowered, so a CPU run, and the dry-run on fake
CPU devices, never see the kernel, and a compile for a described TPU does.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from .config import ModelConfig, YarnConfig
from .layers import apply_rope, dense_init, softcap

Params = Dict[str, jnp.ndarray]


def init_attention(key, cfg: ModelConfig) -> Params:
    d, hd = cfg.d_model, cfg.head_dim
    q_out = cfg.num_heads * hd
    kv_out = cfg.num_kv_heads * hd
    ks = jax.random.split(key, 4)
    params = {
        "wq": dense_init(ks[0], (d, q_out), dtype=jnp.dtype(cfg.param_dtype)),
        "wk": dense_init(ks[1], (d, kv_out), dtype=jnp.dtype(cfg.param_dtype)),
        "wv": dense_init(ks[2], (d, kv_out), dtype=jnp.dtype(cfg.param_dtype)),
        "wo": dense_init(ks[3], (q_out, d), in_axis_size=q_out, dtype=jnp.dtype(cfg.param_dtype)),
    }
    if cfg.use_bias_attn:
        params["bq"] = jnp.zeros((q_out,), jnp.dtype(cfg.param_dtype))
        params["bk"] = jnp.zeros((kv_out,), jnp.dtype(cfg.param_dtype))
        params["bv"] = jnp.zeros((kv_out,), jnp.dtype(cfg.param_dtype))
        params["bo"] = jnp.zeros((d,), jnp.dtype(cfg.param_dtype))
    return params


def _project_qkv(params: Params, x, cfg: ModelConfig):
    B, S, _ = x.shape
    dt = cfg.compute_dtype
    q = x @ params["wq"].astype(dt)
    k = x @ params["wk"].astype(dt)
    v = x @ params["wv"].astype(dt)
    if cfg.use_bias_attn:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


def _out_proj(params: Params, attn_out, cfg: ModelConfig):
    B, S = attn_out.shape[:2]
    dt = cfg.compute_dtype
    y = attn_out.reshape(B, S, cfg.num_heads * cfg.head_dim) @ params["wo"].astype(dt)
    if cfg.use_bias_attn:
        y = y + params["bo"].astype(dt)
    return y


def _sdpa_dense(
    q, k, v, *, q_positions, k_positions, window, logit_softcap
) -> jnp.ndarray:
    """Fully materialized masked attention with GQA head grouping."""
    B, Sq, H, hd = q.shape
    KVH = k.shape[2]
    groups = H // KVH
    qg = q.reshape(B, Sq, KVH, groups, hd)
    scale = hd ** -0.5
    # bf16 operands, f32 accumulation (MXU-native): upcasting q/k to f32
    # materializes f32 copies that XLA then all-gathers at double width
    # under tensor parallelism (§Perf arctic iteration 4).
    logits = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32
    )
    logits = logits * scale
    logits = softcap(logits, logit_softcap)
    mask = k_positions[None, :] <= q_positions[:, None]  # causal
    mask &= k_positions[None, :] >= 0  # empty cache slots
    if window is not None:
        mask &= k_positions[None, :] > q_positions[:, None] - window
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out.reshape(B, Sq, H, hd)


def _sdpa_chunked(
    q, k, v, *, q_positions, k_positions, window, logit_softcap, block
) -> jnp.ndarray:
    """Query-block-sequential attention: flash-style O(block*Sk) memory.

    Scans over query blocks; each block attends to the full key range (or,
    for windowed attention, a ``window + block`` slice around the block —
    both the memory footprint and the FLOPs of sliding-window attention
    then scale with the window, not the sequence).

    NOTE for the dry-run roofline: ``cost_analysis`` counts the scan body
    once, so per-layer attention FLOPs must be corrected analytically
    (launch/dryrun.py::attn_flops).
    """
    B, Sq, H, hd = q.shape
    assert Sq % block == 0, (Sq, block)
    nb = Sq // block
    qb = jnp.moveaxis(q.reshape(B, nb, block, H, hd), 1, 0)  # [nb, B, blk, H, hd]
    pb = q_positions.reshape(nb, block)
    starts = jnp.arange(nb) * block

    kv_span = None if window is None else window + block

    def body(_, inp):
        qi, pi, start = inp
        if kv_span is None or kv_span >= k.shape[1]:
            ki, vi, kpi = k, v, k_positions
        else:
            # keys for queries [start, start+block) live in
            # [start - window + 1, start + block); clamp to array bounds —
            # the positional mask squelches any overhang.
            s = jnp.clip(start - (kv_span - block), 0, k.shape[1] - kv_span)
            ki = jax.lax.dynamic_slice_in_dim(k, s, kv_span, axis=1)
            vi = jax.lax.dynamic_slice_in_dim(v, s, kv_span, axis=1)
            kpi = jax.lax.dynamic_slice_in_dim(k_positions, s, kv_span, axis=0)
        out = _sdpa_dense(
            qi, ki, vi,
            q_positions=pi, k_positions=kpi,
            window=window, logit_softcap=logit_softcap,
        )
        return None, out

    # checkpoint the block: without it, scan's backward saves each block's
    # softmax probs — re-materializing the full (Sq, Sk) matrix the chunking
    # exists to avoid.  Recompute-in-backward is the flash-attention deal.
    body = jax.checkpoint(body)
    _, outs = jax.lax.scan(body, None, (qb, pb, starts))
    return jnp.moveaxis(outs, 0, 1).reshape(B, Sq, H, hd)


def _sdpa_chunked_kv(
    q, k, v, *, q_positions, k_positions, window, logit_softcap, block
) -> jnp.ndarray:
    """KV-block-sequential flash attention (online softmax in pure jnp).

    Scans over KEY blocks carrying running (max, normalizer, accumulator);
    queries are never reshaped or re-laid out, so a sequence-sharded q
    flows straight through under SP — only k/v (2*kv_heads*head_dim wide
    vs d_model for activations) need the sequence gather.  This is the
    same schedule as the Pallas flash kernel, expressed to XLA.
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    assert Sk % block == 0, (Sk, block)
    nb = Sk // block
    KVH = k.shape[2]
    groups = H // KVH
    qg = q.reshape(B, Sq, KVH, groups, hd)
    scale = hd ** -0.5
    kb = jnp.moveaxis(k.reshape(B, nb, block, KVH, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nb, block, KVH, hd), 1, 0)
    pb = k_positions.reshape(nb, block)

    @jax.checkpoint
    def body(carry, inp):
        m_prev, l_prev, acc = carry
        ki, vi, kpi = inp
        logits = jnp.einsum(
            "bqhgd,bkhd->bhgqk", qg, ki, preferred_element_type=jnp.float32
        ) * scale
        logits = softcap(logits, logit_softcap)
        mask = kpi[None, :] <= q_positions[:, None]
        mask &= kpi[None, :] >= 0
        if window is not None:
            mask &= kpi[None, :] > q_positions[:, None] - window
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        m_new = jnp.maximum(m_prev, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(vi.dtype), vi,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc), None

    init = (
        jnp.full((B, KVH, groups, Sq), -1e30, jnp.float32),
        jnp.zeros((B, KVH, groups, Sq), jnp.float32),
        jnp.zeros((B, KVH, groups, Sq, hd), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(body, init, (kb, vb, pb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.moveaxis(out, -2, 1)  # [B, Sq, KVH, groups, hd]
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def sdpa(
    q,  # [B, Sq, H, hd]
    k,  # [B, Sk, KVH, hd]
    v,  # [B, Sk, KVH, hd]
    *,
    q_positions,  # [Sq] absolute positions of queries
    k_positions,  # [Sk] absolute positions of keys (-1 = empty cache slot)
    window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    impl: str = "auto",
    block: int = 512,
) -> jnp.ndarray:
    """Masked scaled-dot-product attention with GQA head grouping.

    Causality and windowing are expressed purely through positions, so the
    same code serves training (q_positions == k_positions == arange) and
    decode (one query against a rolling cache with slot positions).
    """
    Sq, Sk = q.shape[1], k.shape[1]
    if impl == "chunked_kv" or (
        impl == "auto" and Sq >= 2 * block and Sq % block == 0 and Sk % block == 0
    ):
        return _sdpa_chunked_kv(
            q, k, v, q_positions=q_positions, k_positions=k_positions,
            window=window, logit_softcap=logit_softcap, block=block,
        )
    if impl == "chunked" or (impl == "auto" and Sq >= 2 * block and Sq % block == 0):
        return _sdpa_chunked(
            q, k, v, q_positions=q_positions, k_positions=k_positions,
            window=window, logit_softcap=logit_softcap, block=block,
        )
    return _sdpa_dense(
        q, k, v, q_positions=q_positions, k_positions=k_positions,
        window=window, logit_softcap=logit_softcap,
    )


def _flash_block(seq: int) -> int:
    """The kernel's query and key block: the widest of 512, 256, 128 that divides ``seq``."""
    return next(b for b in (512, 256, 128) if seq % b == 0)


def _splash_kernel(seq: int, heads: int, window, logit_softcap, interpret: bool):
    # built afresh in each trace (its mask tables are staged into the trace);
    # splash caches the mask processing itself
    if window is None:
        mask = splash.CausalMask((seq, seq))
    else:  # keys in (q - window, q], as sdpa's positional mask
        mask = splash.LocalMask((seq, seq), window_size=(window - 1, 0), offset=0)
    b = _flash_block(seq)
    # seq-minor operands: the layout XLA gives q, k and v around the kernel
    # (rotary halves, cache writes) without padding hd to 128 lanes
    seq_minor = splash.QKVLayout.SEQ_MINOR
    sizes = splash.BlockSizes(  # one backward kernel computes dq, dk and dv
        block_q=b, block_kv=b, block_q_dkv=b, block_kv_dkv=b, use_fused_bwd_kernel=True,
        q_layout=seq_minor, k_layout=seq_minor, v_layout=seq_minor,
    )
    return splash.make_splash_mha(
        splash.MultiHeadMask([mask] * heads), block_sizes=sizes,
        head_shards=1, q_seq_shards=1,
        attn_logits_soft_cap=logit_softcap, interpret=interpret,
    )


def _sdpa_flash(q, k, v, *, window, logit_softcap, interpret: bool = False):
    """Causal self-attention over positions 0..S-1 in JAX's Pallas splash
    kernel, forward and backward: score tiles stay in VMEM, and blocks the
    mask hides are skipped.  q: [B, S, H, hd]; k, v: [B, S, KVH, hd].

    The kernel takes no scale, so q is scaled by ``hd ** -0.5`` in float32
    before it.  Operands are in the compute dtype, with f32 accumulation and
    f32 softmax statistics, as in the jnp forms, except under
    differentiation, where q, and so the kernel's output, stay float32: the
    backward takes each row's sum of ``o * do`` from that output, and a bf16
    output leaves an error in rows of the score gradient that should sum to
    zero, which the keys' gradient (a key bias under rotary positions)
    collects.  Each device runs the kernel on its own rows and heads
    (``map_batch_shards``).

    Grouped-query attention (KVH < H) goes to the same kernel unchanged:
    splash reads key and value head ``h // (H // KVH)`` for query head
    ``h``, forward and in the fused backward, which sums each key and value
    head's gradient over its group of query heads.
    """
    from repro.distributed.act_sharding import map_batch_shards

    def local(q, k, v):
        heads_first = functools.partial(jnp.swapaxes, axis1=1, axis2=2)

        def attend(q_dtype):
            def run(q, k, v):
                kernel = _splash_kernel(q.shape[1], q.shape[2], window, logit_softcap, interpret)
                q = (q.astype(jnp.float32) * q.shape[-1] ** -0.5).astype(q_dtype)
                out = jax.vmap(kernel)(heads_first(q), heads_first(k), heads_first(v))
                return heads_first(out).astype(k.dtype)
            return run

        rounded, exact = attend(k.dtype), attend(jnp.float32)
        fn = jax.custom_vjp(rounded)
        fn.defvjp(lambda q, k, v: jax.vjp(exact, q, k, v), lambda vjp, do: vjp(do))
        return fn(q, k, v)

    with jax.named_scope("flash"):
        return map_batch_shards(local, q, k, v)


def causal_self_attention(q, k, v, cfg: ModelConfig, *, window: Optional[int]):
    """Attention of positions 0..S-1 to themselves (module docstring)."""
    positions = jnp.arange(q.shape[1])

    def jnp_form(q, k, v):
        return sdpa(
            q, k, v,
            q_positions=positions, k_positions=positions,
            window=window, logit_softcap=cfg.attn_logit_softcap,
            impl=cfg.attn_impl, block=cfg.attn_block,
        )

    if cfg.attn_impl != "auto" or q.shape[1] % 128:
        return jnp_form(q, k, v)
    kernel = functools.partial(
        _sdpa_flash, window=window, logit_softcap=cfg.attn_logit_softcap
    )
    return jax.lax.platform_dependent(q, k, v, tpu=kernel, default=jnp_form)


def attention_forward(
    params: Params,
    x,  # [B, S, D]: positions 0..S-1
    cfg: ModelConfig,
    *,
    window: Optional[int],
    yarn: Optional[YarnConfig] = None,  # the layer's rotary (default without)
    return_cache: bool = False,
    cache_len: Optional[int] = None,  # total decode capacity (>= S)
):
    """Training / prefill attention; optionally returns the KV cache."""
    from repro.distributed.act_sharding import replicate_seq

    positions = jnp.arange(x.shape[1])
    q, k, v = _project_qkv(params, x, cfg)
    # sequence-parallel: q stays seq-sharded through the KV-block scan;
    # only k/v (2*kv_heads*head_dim wide, vs d_model for activations) are
    # gathered across the sequence — d_model/(2*kv*hd) ~ 7x fewer bytes
    # than all-gathering activations (§Perf yi-34b iteration 2).
    k, v = replicate_seq(k), replicate_seq(v)
    rope = dict(theta=cfg.rope_theta, fraction=cfg.rope_fraction, yarn=yarn)
    q = apply_rope(q, positions, **rope)
    k = apply_rope(k, positions, **rope)
    out = causal_self_attention(q, k, v, cfg, window=window)
    y = _out_proj(params, out, cfg)
    if not return_cache:
        return y, None
    with jax.named_scope("kv_cache"):
        cache = make_cache_from_prefill(
            k, v, positions, window=window, max_len=cache_len or x.shape[1]
        )
    return y, cache


# -- KV cache ------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int, *, window: Optional[int]):
    """Empty rolling cache.  ``size = min(window, max_len)`` slots."""
    size = max_len if window is None else min(window, max_len)
    dt = cfg.compute_dtype
    return {
        "k": jnp.zeros((batch, size, cfg.num_kv_heads, cfg.head_dim), dt),
        "v": jnp.zeros((batch, size, cfg.num_kv_heads, cfg.head_dim), dt),
        "pos": jnp.full((size,), -1, jnp.int32),  # absolute position per slot
    }


def make_cache_from_prefill(k, v, positions, *, window: Optional[int], max_len: int):
    """Cache holding the (windowed tail of the) prefill keys/values.

    The cache is sized for ``max_len`` total positions and laid out so that
    absolute position ``p`` occupies slot ``p % size`` — the invariant
    :func:`attention_decode` relies on when it writes new tokens.
    """
    n = k.shape[1]
    size = max_len if window is None else min(window, max_len)
    positions = positions.astype(jnp.int32)
    if n > size:  # keep only the windowed tail
        k, v, positions = k[:, -size:], v[:, -size:], positions[-size:]
        n = size
    if n < size:  # pad to capacity; empty slots flagged with pos = -1
        pad = size - n
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        positions = jnp.pad(positions, (0, pad), constant_values=-1)
    # roll so that entry holding absolute position p sits at slot p % size
    first = positions[0]
    shift = jnp.where(first > 0, first % size, 0)
    k = jnp.roll(k, shift, axis=1)
    v = jnp.roll(v, shift, axis=1)
    positions = jnp.roll(positions, shift, axis=0)
    return {"k": k, "v": v, "pos": positions}


def attention_decode(
    params: Params,
    x_t,  # [B, 1, D]
    cache,
    cfg: ModelConfig,
    position,  # scalar int32: absolute position of the new token
    *,
    window: Optional[int],
    yarn: Optional[YarnConfig] = None,
):
    """One decode step against (and updating) a rolling KV cache."""
    q, k_new, v_new = _project_qkv(params, x_t, cfg)
    pos_arr = jnp.full((1,), position, dtype=jnp.int32)
    rope = dict(theta=cfg.rope_theta, fraction=cfg.rope_fraction, yarn=yarn)
    q = apply_rope(q, pos_arr, **rope)
    k_new = apply_rope(k_new, pos_arr, **rope)
    size = cache["k"].shape[1]
    slot = position % size  # rolling for windows; affine for full caches
    with jax.named_scope("kv_cache"):
        k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot, axis=1)
        pos = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], pos_arr, slot, axis=0
        )
    out = sdpa(
        q, k, v,
        q_positions=pos_arr, k_positions=pos,
        window=window, logit_softcap=cfg.attn_logit_softcap,
    )
    y = _out_proj(params, out, cfg)
    return y, {"k": k, "v": v, "pos": pos}
