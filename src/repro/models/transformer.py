"""Unified decoder stack covering all ten assigned architectures.

The layer stack is ``cfg.pattern`` repeated ``cfg.num_groups`` times (+ a
small unrolled remainder), executed with ``lax.scan`` over the groups so
compile time stays flat in depth.  Each *slot* of the pattern owns its own
stacked parameters, so heterogeneous patterns like RecurrentGemma's
(recurrent, recurrent, local_attn) scan cleanly.

Entry points (all pure functions over a params pytree):

* :func:`init_params`
* :func:`forward`      — training/prefill forward -> (logits, aux)
* :func:`prefill`      — forward + per-layer KV/recurrent caches
* :func:`decode_step`  — one token through the cache pytree
* :func:`loss_fn`      — next-token CE (+ router aux, z-loss)

Named scopes (``jax.named_scope``: compile-time metadata, which a profile
shows as each operation's path) mark ``embed``, ``layers`` (the stack's own
work), ``attention`` and ``ffn`` in each block (inside a held-expert
``ffn``: ``moe_router``, ``moe_dispatch``, ``moe_experts``,
``moe_combine``), ``head``, ``loss``, ``kv_cache`` and the whole of
``prefill`` and ``decode``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import attention_decode, attention_forward, init_attention, init_cache
from .config import ATTN, LOCAL, RECURRENT, RWKV, ModelConfig
from .ffn import dense_ffn, init_dense_ffn, init_moe, moe_ffn
from .layers import apply_norm, dense_init, embed_init, init_norm, softcap
from .rglru import init_rglru_block, init_rglru_state, rglru_block
from .rwkv6 import (
    channel_mix,
    init_rwkv_block,
    init_rwkv_state,
    time_mix,
)

Params = Dict[str, Any]
IGNORE_LABEL = -100


# -- per-kind layer init ---------------------------------------------------------


def _init_layer(key, kind: str, cfg: ModelConfig) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    params: Params = {"norm1": init_norm(k3, cfg), "norm2": init_norm(k4, cfg)}
    if kind in (ATTN, LOCAL):
        params["attn"] = init_attention(k1, cfg)
        if cfg.moe is not None:
            params["ffn"] = init_moe(k2, cfg)
        else:
            params["ffn"] = init_dense_ffn(k2, cfg)
    elif kind == RECURRENT:
        params["rec"] = init_rglru_block(k1, cfg)
        params["ffn"] = init_dense_ffn(k2, cfg)
    elif kind == RWKV:
        params["rwkv"] = init_rwkv_block(k1, cfg)
    else:
        raise ValueError(kind)
    return params


def init_params(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, 8)
    # Embedding tables stay fp32 even under bf16 params: standard for
    # quality, and the fp32->bf16 convert between table and token gather is
    # load-bearing — without it the gather's operand is the sharded
    # parameter itself, which XLA's SPMD partitioner CHECK-fails on under
    # a manual "pod" sub-mesh (see distributed/act_sharding.py).
    params: Params = {
        "embed": embed_init(keys[0], (cfg.vocab_size, cfg.d_model), dtype=jnp.float32),
        "final_norm": init_norm(keys[1], cfg),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(
            keys[2], (cfg.d_model, cfg.vocab_size), dtype=jnp.float32
        )
    if cfg.frontend in ("patch", "frame"):
        params["frontend_proj"] = dense_init(
            keys[3], (cfg.frontend_dim, cfg.d_model), dtype=jnp.float32
        )
    # scanned groups: one stacked tree per pattern slot
    if cfg.num_groups > 0:
        slots = {}
        slot_keys = jax.random.split(keys[4], len(cfg.pattern))
        for s, kind in enumerate(cfg.pattern):
            gkeys = jax.random.split(slot_keys[s], cfg.num_groups)
            slots[f"slot{s}"] = jax.vmap(lambda k, kind=kind: _init_layer(k, kind, cfg))(gkeys)
        params["groups"] = slots
    # unrolled remainder layers
    if cfg.remainder:
        rkeys = jax.random.split(keys[5], len(cfg.remainder))
        params["remainder"] = [
            _init_layer(rkeys[i], kind, cfg) for i, kind in enumerate(cfg.remainder)
        ]
    return params


# -- blocks ----------------------------------------------------------------------


def _layer_window(kind: str, cfg: ModelConfig) -> Optional[int]:
    if kind == LOCAL:
        return cfg.local_window
    if kind == ATTN:
        return cfg.window
    return None


def _layer_attention(kind: str, cfg: ModelConfig) -> Dict[str, Any]:
    """The window and the rotary of a layer kind: YaRN's in the kinds
    ``cfg.yarn`` names, else the default."""
    yarn = cfg.yarn if cfg.yarn is not None and kind in cfg.yarn.kinds else None
    return {"window": _layer_window(kind, cfg), "yarn": yarn}


def _block_train(params: Params, x, kind: str, cfg: ModelConfig):
    """One layer (training/prefill, no cache). Returns (x, aux, pairs):
    the MoE balance loss, and the (token, expert) pairs a held-expert layer
    computed (None for every other layer)."""
    aux = jnp.zeros((), jnp.float32)
    pairs = None
    if kind in (ATTN, LOCAL):
        with jax.named_scope("attention"):
            h = apply_norm(params["norm1"], x, cfg)
            attn_out, _ = attention_forward(params["attn"], h, cfg, **_layer_attention(kind, cfg))
        x = x + attn_out
        with jax.named_scope("ffn"):
            h = apply_norm(params["norm2"], x, cfg)
            if cfg.moe is not None:
                ffn_out, aux, pairs = moe_ffn(params["ffn"], h, cfg)
            else:
                ffn_out = dense_ffn(params["ffn"], h, cfg)
        x = x + ffn_out
    elif kind == RECURRENT:
        b = x.shape[0]
        h = apply_norm(params["norm1"], x, cfg)
        rec_out, _ = rglru_block(params["rec"], h, cfg, state=init_rglru_state(cfg, b))
        x = x + rec_out
        with jax.named_scope("ffn"):
            h = apply_norm(params["norm2"], x, cfg)
            ffn_out = dense_ffn(params["ffn"], h, cfg)
        x = x + ffn_out
    elif kind == RWKV:
        b = x.shape[0]
        st = init_rwkv_state(cfg, b)
        h = apply_norm(params["norm1"], x, cfg)
        tm_out, _, _ = time_mix(
            params["rwkv"], h, cfg, shift_state=st["shift_att"], wkv_state=st["wkv"]
        )
        x = x + tm_out
        h = apply_norm(params["norm2"], x, cfg)
        cm_out, _ = channel_mix(params["rwkv"], h, cfg, shift_state=st["shift_ffn"])
        x = x + cm_out
    else:
        raise ValueError(kind)
    return x, aux, pairs


def _block_prefill(params: Params, x, kind: str, cfg: ModelConfig, max_len: int):
    """One layer, returning its decode cache."""
    if kind in (ATTN, LOCAL):
        with jax.named_scope("attention"):
            h = apply_norm(params["norm1"], x, cfg)
            attn_out, cache = attention_forward(
                params["attn"], h, cfg, **_layer_attention(kind, cfg),
                return_cache=True, cache_len=max_len,
            )
        x = x + attn_out
        with jax.named_scope("ffn"):
            h = apply_norm(params["norm2"], x, cfg)
            if cfg.moe is not None:
                ffn_out = moe_ffn(params["ffn"], h, cfg)[0]
            else:
                ffn_out = dense_ffn(params["ffn"], h, cfg)
        x = x + ffn_out
        return x, cache
    if kind == RECURRENT:
        b = x.shape[0]
        h = apply_norm(params["norm1"], x, cfg)
        rec_out, state = rglru_block(params["rec"], h, cfg, state=init_rglru_state(cfg, b))
        x = x + rec_out
        with jax.named_scope("ffn"):
            h = apply_norm(params["norm2"], x, cfg)
            ffn_out = dense_ffn(params["ffn"], h, cfg)
        x = x + ffn_out
        return x, state
    if kind == RWKV:
        b = x.shape[0]
        st = init_rwkv_state(cfg, b)
        h = apply_norm(params["norm1"], x, cfg)
        tm_out, shift_att, wkv = time_mix(
            params["rwkv"], h, cfg, shift_state=st["shift_att"], wkv_state=st["wkv"]
        )
        x = x + tm_out
        h = apply_norm(params["norm2"], x, cfg)
        cm_out, shift_ffn = channel_mix(params["rwkv"], h, cfg, shift_state=st["shift_ffn"])
        x = x + cm_out
        return x, {"wkv": wkv, "shift_att": shift_att, "shift_ffn": shift_ffn}
    raise ValueError(kind)


def _block_decode(params: Params, x_t, cache, kind: str, cfg: ModelConfig, position):
    """One layer, one token. Returns (x_t, new_cache)."""
    if kind in (ATTN, LOCAL):
        with jax.named_scope("attention"):
            h = apply_norm(params["norm1"], x_t, cfg)
            attn_out, cache = attention_decode(
                params["attn"], h, cache, cfg, position, **_layer_attention(kind, cfg)
            )
        x_t = x_t + attn_out
        with jax.named_scope("ffn"):
            h = apply_norm(params["norm2"], x_t, cfg)
            if cfg.moe is not None:
                ffn_out = moe_ffn(params["ffn"], h, cfg)[0]
            else:
                ffn_out = dense_ffn(params["ffn"], h, cfg)
        return x_t + ffn_out, cache
    if kind == RECURRENT:
        h = apply_norm(params["norm1"], x_t, cfg)
        rec_out, state = rglru_block(params["rec"], h, cfg, state=cache)
        x_t = x_t + rec_out
        with jax.named_scope("ffn"):
            h = apply_norm(params["norm2"], x_t, cfg)
            ffn_out = dense_ffn(params["ffn"], h, cfg)
        return x_t + ffn_out, state
    if kind == RWKV:
        h = apply_norm(params["norm1"], x_t, cfg)
        tm_out, shift_att, wkv = time_mix(
            params["rwkv"], h, cfg, shift_state=cache["shift_att"], wkv_state=cache["wkv"]
        )
        x_t = x_t + tm_out
        h = apply_norm(params["norm2"], x_t, cfg)
        cm_out, shift_ffn = channel_mix(
            params["rwkv"], h, cfg, shift_state=cache["shift_ffn"]
        )
        x_t = x_t + cm_out
        return x_t, {"wkv": wkv, "shift_att": shift_att, "shift_ffn": shift_ffn}
    raise ValueError(kind)


# -- embedding / frontends -------------------------------------------------------


def embed_inputs(params: Params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig):
    """Token + stub-frontend embedding -> x [B, S, D], for positions 0..S-1."""
    from repro.distributed.act_sharding import shard_activations

    dt = cfg.compute_dtype
    with jax.named_scope("embed"):
        if cfg.frontend == "frame":
            x = batch["frame_embeds"].astype(dt) @ params["frontend_proj"].astype(dt)
        else:
            x = params["embed"].astype(dt)[batch["tokens"]]
            if cfg.frontend == "patch":
                patches = batch["patch_embeds"].astype(dt) @ params["frontend_proj"].astype(dt)
                x = jnp.concatenate([patches, x], axis=1)
        x = shard_activations(x)  # batch dim -> ("pod",)"data" per active context
    return x


def unembed(params: Params, x, cfg: ModelConfig):
    dt = cfg.compute_dtype
    with jax.named_scope("head"):
        h = apply_norm(params["final_norm"], x, cfg)
        if cfg.tie_embeddings:
            logits = h @ params["embed"].astype(dt).T
        else:
            logits = h @ params["unembed"].astype(dt)
        return softcap(logits, cfg.logits_softcap)


# -- full-stack passes -----------------------------------------------------------


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat in ("full", "block"):
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return fn


def _forward(params: Params, batch, cfg: ModelConfig):
    """Full forward pass -> (logits [B, S, V], moe_aux scalar, pairs): the
    (token, expert) pairs the held-expert layers computed, summed over the
    layers (None where no layer is one)."""
    x = embed_inputs(params, batch, cfg)

    from repro.distributed.act_sharding import shard_activations

    def add(pairs, p):
        return pairs if p is None else pairs + p

    # remat "full" checkpoints the scan body, one group of ``pattern``;
    # "block" each block inside it, so that a backward holds the residuals
    # of one layer, not of a whole group
    per_block = cfg.remat == "block"

    def block(params, x, kind):
        fn = lambda params, x: _block_train(params, x, kind, cfg)  # noqa: E731
        return (_maybe_remat(fn, cfg) if per_block else fn)(params, x)

    def group_body(carry, slot_params):
        x, aux, pairs = carry
        for s, kind in enumerate(cfg.pattern):
            x, a, p = block(slot_params[f"slot{s}"], x, kind)
            aux, pairs = aux + a, add(pairs, p)
        # sequence-parallel boundary: the scan carry (= remat residual)
        # lives sharded over (batch, seq) between blocks.
        return (shard_activations(x), aux, pairs), None

    aux = jnp.zeros((), jnp.float32)
    held = cfg.moe is not None and cfg.moe.impl == "held"
    pairs = jnp.zeros((), jnp.int32) if held else None  # None: no leaf in the carry
    # "layers": the layer stack's own work, such as the scan's slicing of
    # stacked weights and its stacking of residuals, outside any one block
    with jax.named_scope("layers"):
        if cfg.num_groups > 0:
            body = group_body if per_block else _maybe_remat(group_body, cfg)
            if cfg.scan_layers:
                (x, aux, pairs), _ = jax.lax.scan(body, (x, aux, pairs), params["groups"])
            else:  # unrolled: used by the dry-run cost probes
                for i in range(cfg.num_groups):
                    slot_i = jax.tree.map(lambda a, i=i: a[i], params["groups"])
                    (x, aux, pairs), _ = body((x, aux, pairs), slot_i)
        for i, kind in enumerate(cfg.remainder):
            x, a, p = _block_train(params["remainder"][i], x, kind, cfg)
            aux, pairs = aux + a, add(pairs, p)
    logits = unembed(params, x, cfg)
    return logits, aux, pairs


def forward(params: Params, batch, cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full forward pass -> (logits [B, S, V], moe_aux scalar)."""
    return _forward(params, batch, cfg)[:2]


def loss_fn(params: Params, batch, cfg: ModelConfig):
    """Next-token cross-entropy with label masking and aux losses; with
    held-expert layers, ``metrics["moe_pairs"]`` counts the (token, expert)
    pairs they computed."""
    logits, aux, pairs = _forward(params, batch, cfg)
    labels = batch["labels"]
    with jax.named_scope("loss"):
        # standard causal shift: logits[t] predicts labels[t]
        logits = logits[:, : labels.shape[1], :]
        mask = (labels != IGNORE_LABEL).astype(jnp.float32)
        safe_labels = jnp.maximum(labels, 0)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        token_ll = jnp.take_along_axis(logp, safe_labels[..., None], axis=-1)[..., 0]
        denom = jnp.maximum(mask.sum(), 1.0)
        ce = -(token_ll * mask).sum() / denom
        total = ce
        if cfg.z_loss:
            logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
            zl = cfg.z_loss * jnp.mean(jnp.square(logz) * mask)
            total = total + zl
        if cfg.moe is not None:
            total = total + cfg.moe.router_aux_coef * aux
    metrics = {"ce": ce, "aux": aux, "tokens": denom}
    if pairs is not None:
        metrics["moe_pairs"] = pairs
    return total, metrics


def prefill(params: Params, batch, cfg: ModelConfig, *, max_len: Optional[int] = None):
    """Forward + caches. Returns (last-position logits [B, V], cache pytree)."""
    with jax.named_scope("prefill"):
        x = embed_inputs(params, batch, cfg)
        max_len = max_len or x.shape[1]

        from repro.distributed.act_sharding import shard_activations

        def group_body(x, slot_params):
            caches = {}
            for s, kind in enumerate(cfg.pattern):
                x, cache = _block_prefill(slot_params[f"slot{s}"], x, kind, cfg, max_len)
                caches[f"slot{s}"] = cache
            return shard_activations(x), caches

        cache: Params = {}
        with jax.named_scope("layers"):
            if cfg.num_groups > 0:
                if cfg.scan_layers:
                    x, cache["groups"] = jax.lax.scan(group_body, x, params["groups"])
                else:
                    caches = []
                    for i in range(cfg.num_groups):
                        slot_i = jax.tree.map(lambda a, i=i: a[i], params["groups"])
                        x, c = group_body(x, slot_i)
                        caches.append(c)
                    cache["groups"] = jax.tree.map(lambda *xs: jnp.stack(xs), *caches)
            rem = []
            for i, kind in enumerate(cfg.remainder):
                x, c = _block_prefill(params["remainder"][i], x, kind, cfg, max_len)
                rem.append(c)
        if rem:
            cache["remainder"] = rem
        logits = unembed(params, x[:, -1:, :], cfg)[:, 0, :]
        return logits, cache


def init_decode_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    """Zero-filled cache pytree matching :func:`prefill`'s output."""

    def one(kind: str):
        if kind in (ATTN, LOCAL):
            return init_cache(cfg, batch, max_len, window=_layer_window(kind, cfg))
        if kind == RECURRENT:
            return init_rglru_state(cfg, batch)
        return init_rwkv_state(cfg, batch)

    cache: Params = {}
    if cfg.num_groups > 0:
        cache["groups"] = {
            f"slot{s}": jax.tree.map(
                lambda a: jnp.broadcast_to(a, (cfg.num_groups,) + a.shape), one(kind)
            )
            for s, kind in enumerate(cfg.pattern)
        }
    if cfg.remainder:
        cache["remainder"] = [one(kind) for kind in cfg.remainder]
    return cache


def decode_step(params: Params, tokens_t, cache, cfg: ModelConfig, position):
    """One decode step.

    tokens_t: [B] token ids (or [B, 1, frontend_dim] embeddings for the
    "frame" stub); position: scalar absolute position.
    Returns (logits [B, V], new cache).
    """
    with jax.named_scope("decode"):
        from repro.distributed.act_sharding import shard_activations

        dt = cfg.compute_dtype
        with jax.named_scope("embed"):
            if cfg.frontend == "frame":
                x_t = tokens_t.astype(dt) @ params["frontend_proj"].astype(dt)
            else:
                x_t = params["embed"].astype(dt)[tokens_t][:, None, :]
            x_t = shard_activations(x_t)

        def group_body(x_t, xs):
            slot_params, slot_cache = xs
            new_caches = {}
            for s, kind in enumerate(cfg.pattern):
                x_t, nc = _block_decode(
                    slot_params[f"slot{s}"], x_t, slot_cache[f"slot{s}"], kind, cfg, position
                )
                new_caches[f"slot{s}"] = nc
            return x_t, new_caches

        new_cache: Params = {}
        with jax.named_scope("layers"):
            if cfg.num_groups > 0:
                if cfg.scan_layers:
                    x_t, new_cache["groups"] = jax.lax.scan(
                        group_body, x_t, (params["groups"], cache["groups"])
                    )
                else:
                    caches = []
                    for i in range(cfg.num_groups):
                        take_i = lambda a, i=i: jax.tree.map(lambda v: v[i], a)
                        x_t, c = group_body(x_t, (take_i(params["groups"]), take_i(cache["groups"])))
                        caches.append(c)
                    new_cache["groups"] = jax.tree.map(lambda *xs: jnp.stack(xs), *caches)
            if cfg.remainder:
                rem = []
                for i, kind in enumerate(cfg.remainder):
                    x_t, nc = _block_decode(
                        params["remainder"][i], x_t, cache["remainder"][i], kind, cfg, position
                    )
                    rem.append(nc)
                new_cache["remainder"] = rem
        logits = unembed(params, x_t, cfg)[:, 0, :]
        return logits, new_cache
