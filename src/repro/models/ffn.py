"""Feed-forward blocks: dense (GELU / SwiGLU / GeGLU) and mixture-of-experts.

Two MoE dispatch implementations, selectable per config:

* ``einsum`` — GShard-style grouped dispatch/combine one-hot einsums.
  Tokens are processed in groups of ``group_size`` so the dispatch tensor is
  ``(G, Tg, E, C)`` with per-group capacity ``C = ceil(cf * k * Tg / E)``;
  sharding the group axis over the batch mesh axes and the expert axis over
  the model axis lets GSPMD derive the canonical all-to-all schedule.
  Dispatch-einsum FLOPs are real but small (~2*T*E*C*D vs 6*T*k*D*F expert
  FLOPs); the roofline table reports the ratio.

* ``gather`` — argsort/gather based dispatch that avoids the one-hot
  matmuls entirely (true-FLOPs path, used in the §Perf hillclimb).

* ``held`` — the dropless layer of the experts one chip holds in an
  expert-parallel deployment (``MoEConfig.held`` from ``first_held``): the
  router scores every expert, every (token, held expert) pair is computed
  with no capacity, in matmuls grouped over the held experts, and pairs of
  experts held elsewhere add nothing here.  Its named scopes are
  ``moe_router``, ``moe_dispatch``, ``moe_experts`` and ``moe_combine``.

Arctic's "dense residual" (a small dense FFN in parallel with the MoE) is
supported via ``MoEConfig.parallel_dense``.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig, MoEConfig
from .layers import activation_fn, dense_init

Params = Dict[str, jnp.ndarray]

MOE_GROUP_SIZE = 512  # tokens per dispatch group (GShard "G" dimension)


# -- dense FFN -----------------------------------------------------------------


def init_dense_ffn(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d = cfg.d_model
    f = d_ff if d_ff is not None else cfg.d_ff
    pdt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    if cfg.activation in ("swiglu", "geglu"):
        params = {
            "w_gate": dense_init(ks[0], (d, f), dtype=pdt),
            "w_up": dense_init(ks[1], (d, f), dtype=pdt),
            "w_down": dense_init(ks[2], (f, d), in_axis_size=f, dtype=pdt),
        }
    else:
        params = {
            "w_up": dense_init(ks[0], (d, f), dtype=pdt),
            "w_down": dense_init(ks[1], (f, d), in_axis_size=f, dtype=pdt),
        }
    if cfg.use_bias_mlp:
        params["b_up"] = jnp.zeros((f,), pdt)
        params["b_down"] = jnp.zeros((d,), pdt)
    return params


def dense_ffn(params: Params, x, cfg: ModelConfig):
    dt = cfg.compute_dtype
    if cfg.activation in ("swiglu", "geglu"):
        inner = jax.nn.silu if cfg.activation == "swiglu" else jax.nn.gelu
        gate = inner(x @ params["w_gate"].astype(dt))
        up = x @ params["w_up"].astype(dt)
        if cfg.use_bias_mlp:
            up = up + params["b_up"].astype(dt)
        h = gate * up
    else:
        h = x @ params["w_up"].astype(dt)
        if cfg.use_bias_mlp:
            h = h + params["b_up"].astype(dt)
        h = activation_fn(cfg.activation)(h)
    y = h @ params["w_down"].astype(dt)
    if cfg.use_bias_mlp:
        y = y + params["b_down"].astype(dt)
    return y


# -- mixture of experts ----------------------------------------------------------


def init_moe(key, cfg: ModelConfig) -> Params:
    assert cfg.moe is not None
    moe = cfg.moe
    d, f, e = cfg.d_model, cfg.d_ff, moe.num_held
    pdt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    glu = cfg.activation in ("swiglu", "geglu")
    params: Params = {
        "router": dense_init(ks[0], (d, moe.num_experts), dtype=jnp.float32),
        "w_up": dense_init(ks[1], (e, d, f), in_axis_size=d, dtype=pdt),
        "w_down": dense_init(ks[2], (e, f, d), in_axis_size=f, dtype=pdt),
    }
    if glu:
        params["w_gate"] = dense_init(ks[3], (e, d, f), in_axis_size=d, dtype=pdt)
    if moe.parallel_dense:
        params["dense"] = init_dense_ffn(ks[4], cfg)
    return params


def _router_probs(params: Params, x_flat, moe: MoEConfig):
    """Router logits and softmax in fp32 (the logits' matmul at ``HIGHEST``,
    which a TPU would otherwise take in one bf16 pass, so that a top-k choice
    does not hang on the router weights' rounding) + top-k selection with
    renormalized gates.

    Indices come from a stop-gradient top_k; gate values are recovered by
    one-hot contraction against the differentiable softmax.  This keeps
    gradients flowing to the router while avoiding top_k's scatter-based
    backward, which XLA's SPMD partitioner cannot handle beneath a manual
    "pod" sub-mesh (same CHECK failure as sharded gathers).
    """
    logits = jnp.matmul(
        x_flat.astype(jnp.float32), params["router"], precision=jax.lax.Precision.HIGHEST
    )
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    _, expert_idx = jax.lax.top_k(
        jax.lax.stop_gradient(probs), moe.num_experts_per_tok
    )
    gate_cols = [
        jnp.sum(probs * jax.nn.one_hot(expert_idx[:, j], probs.shape[-1]), axis=-1)
        for j in range(moe.num_experts_per_tok)
    ]
    gate_vals = jnp.stack(gate_cols, axis=-1)  # (T, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    return probs, gate_vals, expert_idx


def _aux_loss(probs, expert_idx, moe: MoEConfig):
    """Switch-style load-balancing loss: E * sum_e f_e * P_e."""
    e = moe.num_experts
    counts = jnp.zeros((e,), jnp.float32)
    for j in range(moe.num_experts_per_tok):
        counts = counts + jnp.sum(
            jax.nn.one_hot(expert_idx[:, j], e, dtype=jnp.float32), axis=0
        )
    f = counts / jnp.maximum(counts.sum(), 1.0)
    p = probs.mean(axis=0)
    return e * jnp.sum(f * p)


def _capacity(tg: int, moe: MoEConfig) -> int:
    c = math.ceil(moe.capacity_factor * moe.num_experts_per_tok * tg / moe.num_experts)
    return max(4, -(-c // 4) * 4)  # round up to a multiple of 4


def _expert_ffn(params: Params, xs, cfg: ModelConfig):
    """xs: (..., E, C, D) -> (..., E, C, D) through per-expert weights."""
    dt = cfg.compute_dtype
    glu = cfg.activation in ("swiglu", "geglu")
    if glu:
        inner = jax.nn.silu if cfg.activation == "swiglu" else jax.nn.gelu
        gate = inner(jnp.einsum("...ecd,edf->...ecf", xs, params["w_gate"].astype(dt)))
        up = jnp.einsum("...ecd,edf->...ecf", xs, params["w_up"].astype(dt))
        h = gate * up
    else:
        h = activation_fn(cfg.activation)(
            jnp.einsum("...ecd,edf->...ecf", xs, params["w_up"].astype(dt))
        )
    return jnp.einsum("...ecf,efd->...ecd", h, params["w_down"].astype(dt))


def _moe_constraint(x, spec_axes):
    """Best-effort sharding constraint on MoE intermediates.

    GSPMD's default schedule for the grouped dispatch einsums all-gathers
    the (g, e, c, d) dispatched activations across the data axis (~18
    GB/device/layer on arctic-480b, measured); pinning groups->data and
    experts->model keeps every einsum local and lets only the weight-grad
    all-reduces cross the fabric.
    """
    from repro.distributed.act_sharding import _SPEC

    spec = _SPEC.get()
    if spec is None:
        return x
    batch_axes, seq_axes = spec
    names = {"G": batch_axes, "E": seq_axes, None: None}
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*(names[a] for a in spec_axes))
    )


def _moe_einsum(params: Params, x_flat, cfg: ModelConfig):
    """GShard grouped dispatch/combine."""
    moe = cfg.moe
    t, d = x_flat.shape
    tg = min(MOE_GROUP_SIZE, t)
    assert t % tg == 0, f"token count {t} not divisible by group size {tg}"
    g = t // tg
    c = _capacity(tg, moe)
    e = moe.num_experts

    probs, gates, expert_idx = _router_probs(params, x_flat, moe)
    aux = _aux_loss(probs, expert_idx, moe)

    # per-group capacity assignment.  dispatch/combine are built directly
    # in the compute dtype: the f32 versions were the largest tensors the
    # backward pass saved and re-gathered (§Perf arctic iteration 2).
    dt = cfg.compute_dtype
    idx_g = expert_idx.reshape(g, tg, moe.num_experts_per_tok)
    gate_g = gates.reshape(g, tg, moe.num_experts_per_tok).astype(dt)
    dispatch = jnp.zeros((g, tg, e, c), dt)
    combine = jnp.zeros((g, tg, e, c), dt)
    counts = jnp.zeros((g, e), jnp.int32)
    for j in range(moe.num_experts_per_tok):
        onehot = jax.nn.one_hot(idx_g[:, :, j], e, dtype=jnp.int32)  # (g, tg, e)
        pos = jnp.cumsum(onehot, axis=1) - 1 + counts[:, None, :]  # (g, tg, e)
        counts = counts + onehot.sum(axis=1)
        # one-hot contraction instead of take_along_axis: gathers with
        # sharded operands CHECK-fail in XLA's partitioner under a manual
        # pod sub-mesh (see distributed/act_sharding.py)
        pos_of_token = jnp.sum(pos * onehot, axis=-1)  # (g, tg)
        keep = pos_of_token < c
        slot_onehot = jax.nn.one_hot(pos_of_token, c, dtype=dt)  # (g, tg, c)
        contrib = (
            onehot.astype(dt)[..., None]
            * slot_onehot[:, :, None, :]
            * keep[..., None, None].astype(dt)
        )
        dispatch = dispatch + contrib
        combine = combine + contrib * gate_g[:, :, j][..., None, None]

    x_g = _moe_constraint(x_flat.reshape(g, tg, d), ("G", None, None))
    dispatch = _moe_constraint(dispatch, ("G", None, "E", None))
    combine = _moe_constraint(combine, ("G", None, "E", None))
    xs = jnp.einsum("gtec,gtd->gecd", dispatch.astype(dt), x_g)  # (g, e, c, d)
    xs = _moe_constraint(xs, ("G", "E", None, None))
    ys = _moe_constraint(_expert_ffn(params, xs, cfg), ("G", "E", None, None))
    y_g = jnp.einsum("gtec,gecd->gtd", combine.astype(dt), ys)
    y_g = _moe_constraint(y_g, ("G", None, None))
    return y_g.reshape(t, d), aux


def _moe_gather(params: Params, x_flat, cfg: ModelConfig):
    """Sort/gather dispatch: no one-hot matmuls (true-FLOPs path)."""
    moe = cfg.moe
    t, d = x_flat.shape
    e = moe.num_experts
    k = moe.num_experts_per_tok
    c = _capacity(t, moe)

    probs, gates, expert_idx = _router_probs(params, x_flat, moe)
    aux = _aux_loss(probs, expert_idx, moe)

    flat_expert = expert_idx.reshape(-1)  # (t*k,)
    flat_gate = gates.reshape(-1).astype(jnp.float32)
    flat_token = jnp.repeat(jnp.arange(t), k)

    # position of each (token, choice) within its expert queue
    onehot = jax.nn.one_hot(flat_expert, e, dtype=jnp.int32)  # (t*k, e)
    pos = jnp.cumsum(onehot, axis=0) - 1
    pos_of = jnp.take_along_axis(pos, flat_expert[:, None], axis=-1)[:, 0]
    keep = pos_of < c
    slot = jnp.where(keep, flat_expert * c + pos_of, e * c)  # overflow -> dropped

    # token index per (expert, capacity) slot; e*c slot table (+1 spill row)
    token_of_slot = jnp.zeros((e * c + 1,), jnp.int32).at[slot].set(flat_token, mode="drop")
    gate_of_slot = jnp.zeros((e * c + 1,), jnp.float32).at[slot].set(flat_gate, mode="drop")
    filled = jnp.zeros((e * c + 1,), jnp.bool_).at[slot].set(keep, mode="drop")

    xs = jnp.take(x_flat, token_of_slot[: e * c], axis=0)  # (e*c, d)
    xs = xs * filled[: e * c, None].astype(xs.dtype)
    ys = _expert_ffn(params, xs.reshape(1, e, c, d), cfg)[0]  # (e, c, d)
    weighted = ys.reshape(e * c, d) * gate_of_slot[: e * c, None].astype(ys.dtype)
    out = jax.ops.segment_sum(weighted, token_of_slot[: e * c], num_segments=t)
    return out.astype(x_flat.dtype), aux


# -- the held experts' dropless layer --------------------------------------------


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


@jax.custom_vjp
def _dispatch(x, src, slot, held):
    """Row ``r`` of the pair buffer is token ``src[r]``: ``x[src]``.

    Its gradient gathers too, where autodiff would scatter-add: each token
    sums the buffer rows ``slot[t, j]`` of its held pairs.
    """
    return x[src]


def _dispatch_fwd(x, src, slot, held):
    return x[src], (slot, held)


def _dispatch_bwd(res, g):
    slot, held = res
    rows = jnp.where(held[..., None], g[slot], 0)  # [t, k, D]
    return jnp.sum(rows, axis=1, dtype=jnp.float32).astype(g.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, w, slot, held, pair):
    """``y[t] = sum_j w[t, j] * ys[slot[t, j]]`` over token t's held pairs.

    Rows of ``ys`` past the held pairs are undefined (the grouped matmul
    leaves them), so they are selected away, never multiplied.  ``pair[r]``
    is the (token, choice) pair ``t * k + j`` of buffer row ``r`` (``t * k``
    on padding); the gradient gathers by it.
    """
    rows = jnp.where(held[..., None], ys[slot], 0).astype(jnp.float32)
    return jnp.sum(w[..., None] * rows, axis=1).astype(ys.dtype)


def _combine_fwd(ys, w, slot, held, pair):
    return _combine(ys, w, slot, held, pair), (ys, w, slot, held, pair)


def _combine_bwd(res, dy):
    ys, w, slot, held, pair = res
    t, k = w.shape
    rows = jnp.where(held[..., None], ys[slot], 0).astype(jnp.float32)
    dw = jnp.sum(dy[:, None, :].astype(jnp.float32) * rows, axis=-1)
    held_r = jnp.append(held.reshape(-1), False)[pair]
    w_r = jnp.append(w.reshape(-1), 0.0)[pair]
    dys = jnp.where(held_r[:, None], w_r[:, None] * dy[jnp.minimum(pair // k, t - 1)], 0)
    return dys.astype(ys.dtype), dw.astype(w.dtype), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _gmm_tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """Tiles of the Pallas grouped matmul: 512 rows (or 128), and for each
    of the other two dimensions its widest divisor that is a multiple of
    128 and at most 1152 (all of it, below 128)."""

    def tile(size: int) -> int:
        return next((t for t in range(min(size, 1152) // 128 * 128, 0, -128) if size % t == 0), size)

    return (512 if m % 512 == 0 else 128), tile(k), tile(n)


def grouped_matmul_kernel(x, w, sizes, *, interpret: bool = False):
    """``x[rows of group g] @ w[g]`` in the Pallas megablox grouped matmul
    (forward and backward), which visits only the tiles that hold the groups'
    rows; rows past the groups are left undefined."""
    from jax.experimental.pallas.ops.tpu.megablox import ops

    return ops.gmm(x, w, sizes, x.dtype, _gmm_tiling, None, None, False, interpret)


def grouped_matmul(x, w, sizes):
    """x: [m, K] rows grouped by ``sizes`` [G] (in order, sum <= m);
    w: [G, K, N].  The Pallas kernel when compiled for a TPU, else
    ``jax.lax.ragged_dot``."""

    def jnp_form(x, w, sizes):
        return jax.lax.ragged_dot(x, w, sizes)

    return jax.lax.platform_dependent(x, w, sizes, tpu=grouped_matmul_kernel, default=jnp_form)


def _held_expert_ffn(params: Params, xs, sizes, cfg: ModelConfig):
    """xs: [m, D] rows grouped by held expert -> [m, D]."""
    dt = cfg.compute_dtype
    mm = functools.partial(grouped_matmul, sizes=sizes)
    up = mm(xs, params["w_up"].astype(dt))
    if cfg.activation in ("swiglu", "geglu"):
        inner = jax.nn.silu if cfg.activation == "swiglu" else jax.nn.gelu
        h = inner(mm(xs, params["w_gate"].astype(dt))) * up
    else:
        h = activation_fn(cfg.activation)(up)
    return mm(h, params["w_down"].astype(dt))


def _moe_held(params: Params, x_flat, cfg: ModelConfig):
    """Dropless layer of the held experts.  Returns (y, aux, pairs computed)."""
    moe = cfg.moe
    t, _ = x_flat.shape
    k, h = moe.num_experts_per_tok, moe.num_held
    with jax.named_scope("moe_router"):
        probs, gates, expert_idx = _router_probs(params, x_flat, moe)
        aux = _aux_loss(probs, expert_idx, moe)
    with jax.named_scope("moe_dispatch"):
        local = expert_idx - moe.first_held
        held = (local >= 0) & (local < h)  # [t, k]
        key = jnp.where(held, local, h).reshape(-1)
        order = jnp.argsort(key, stable=True)  # held pairs first, by expert
        slot = jnp.argsort(order).reshape(t, k)  # buffer row of each pair
        sizes = jnp.sum(jax.nn.one_hot(key, h, dtype=jnp.int32), axis=0)
        # every held pair has a row: a token holds at most min(k, h) of them
        rows = _round_up(t * min(k, h), 128)
        pair = jnp.pad(order, (0, max(rows - t * k, 0)), constant_values=t * k)[:rows]
        slot = jnp.where(held, slot, rows - 1)
        xs = _dispatch(x_flat, jnp.minimum(pair // k, t - 1), slot, held)
    with jax.named_scope("moe_experts"):
        ys = _held_expert_ffn(params, xs, sizes, cfg)
    with jax.named_scope("moe_combine"):
        y = _combine(ys, jnp.where(held, gates, 0.0), slot, held, pair)
    return y, aux, jnp.sum(sizes)


def moe_ffn(params: Params, x, cfg: ModelConfig):
    """MoE feed-forward over x: [B, S, D] -> ([B, S, D], aux_loss, pairs).

    ``pairs`` counts the (token, expert) pairs the held layer computed, and
    is None for the other forms.
    """
    assert cfg.moe is not None
    b, s, d = x.shape
    x_flat = x.reshape(b * s, d)
    pairs = None
    if cfg.moe.impl == "einsum":
        y, aux = _moe_einsum(params, x_flat, cfg)
    elif cfg.moe.impl == "gather":
        y, aux = _moe_gather(params, x_flat, cfg)
    else:
        y, aux, pairs = _moe_held(params, x_flat, cfg)
    y = y.reshape(b, s, d)
    if cfg.moe.parallel_dense:
        y = y + dense_ffn(params["dense"], x, cfg)
    return y, aux, pairs
