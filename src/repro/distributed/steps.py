"""Distributed step builders: train / prefill / decode over any mesh.

Training composes three layers, mirroring the paper's deployment stack:

1. **intra-pod** — GSPMD-automatic: FSDP reduce-scatter over ``data``,
   tensor-parallel collectives over ``model`` (fast ICI);
2. **cross-pod** — explicit, inside a partial-manual ``shard_map`` over the
   ``pod`` axis: this is the WAN, where the ScaleAcross sync strategies
   (allreduce / ps / hier / hier_int8 / local_sgd) apply;
3. **optimizer** — AdamW on the (sharded) pytrees, plus the DiLoCo outer
   step for ``local_sgd``.

Builders return jitted callables plus the sharding trees used, so the
launcher, the dry-run, and the checkpointing layer all agree on placement.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import decode_step as model_decode_step
from repro.models import loss_fn, prefill
from repro.models.config import ModelConfig
from repro.optim.adamw import AdamWConfig, AdamWState, adamw_update, init_adamw
from repro.optim.diloco import DilocoConfig, DilocoState, init_diloco, outer_step

from .act_sharding import activation_sharding
from .compression import init_error_feedback
from .sharding import (
    batch_pspecs,
    batch_shardings,
    cache_shardings,
    params_pspecs,
    params_shardings,
)
from .sync import STRATEGIES, sync_allreduce, sync_hier, sync_hier_int8


class TrainState(NamedTuple):
    adam: AdamWState
    ef: Any  # error-feedback pytree ( () when unused )
    diloco: Any  # DilocoState        ( () when unused )


def init_train_state(
    params, opt_cfg: AdamWConfig, *, strategy: str = "hier"
) -> TrainState:
    return TrainState(
        adam=init_adamw(params),
        ef=init_error_feedback(params) if strategy == "hier_int8" else (),
        diloco=init_diloco(params) if strategy == "local_sgd" else (),
    )


def state_pspecs(params_shapes, mesh: Mesh, *, strategy: str = "hier"):
    """PartitionSpecs for a TrainState matching the params' placement."""
    pspec = params_pspecs(params_shapes, mesh)
    return TrainState(
        adam=AdamWState(step=P(), m=pspec, v=pspec),
        ef=pspec if strategy == "hier_int8" else (),
        diloco=DilocoState(anchor=pspec, momentum=pspec) if strategy == "local_sgd" else (),
    )


def _tree_shardings(pspec_tree, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        pspec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def make_train_step(
    cfg: ModelConfig,
    mesh: Mesh,
    *,
    opt_cfg: Optional[AdamWConfig] = None,
    strategy: str = "hier",
    num_channels: int = 4,
    diloco_cfg: Optional[DilocoConfig] = None,
    params_shapes=None,
    batch_shapes=None,
    donate: bool = True,
):
    """Build the jitted train step for (cfg, mesh, strategy).

    Returns (step_fn, shardings) where
      step_fn(params, state, batch) -> (params, state, metrics)
      shardings = {"params": ..., "state": ..., "batch": ...}
    """
    if strategy not in STRATEGIES:
        raise ValueError(f"strategy {strategy!r} not in {STRATEGIES}")
    opt_cfg = opt_cfg or AdamWConfig()
    diloco_cfg = diloco_cfg or DilocoConfig()
    multi_pod = "pod" in mesh.axis_names

    def inner(params, state: TrainState, batch):
        # batch enters sharded over "pod" only (manual); constrain the
        # embedding output onto "data" so GSPMD spreads activations without
        # partitioning the token-gather indices (XLA CPU partitioner bug —
        # see distributed/act_sharding.py).
        act_axes = "data" if multi_pod else (
            "data" if "data" in mesh.axis_names else None
        )
        seq_axes = "model" if "model" in mesh.axis_names else None
        with activation_sharding(act_axes, seq_axes, mesh):
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: loss_fn(p, batch, cfg), has_aux=True
            )(params)
        new_ef = state.ef
        if multi_pod:
            npods = jax.lax.psum(1, "pod")
            metrics = {k: jax.lax.psum(v, "pod") / npods for k, v in metrics.items()}
            loss = jax.lax.psum(loss, "pod") / npods
            if strategy == "allreduce":
                grads = sync_allreduce(grads)
            elif strategy == "hier":
                grads = sync_hier(grads, num_channels=num_channels)
            elif strategy == "hier_int8":
                grads, new_ef = sync_hier_int8(grads, state.ef)
            elif strategy in ("ps", "local_sgd"):
                pass  # ps: handled after the optimizer; local_sgd: no WAN here

        new_params, new_adam, opt_metrics = adamw_update(
            opt_cfg, grads, state.adam, params
        )
        new_diloco = state.diloco

        if multi_pod and strategy == "ps":
            # pull phase of the parameter server: pod 0 is authoritative,
            # everyone receives its parameters (full WAN broadcast).  The
            # push phase is the all_gather of gradients below.
            gathered = jax.tree.map(
                lambda g: jax.lax.all_gather(g.astype(jnp.float32), "pod"), grads
            )
            g_mean = jax.tree.map(lambda g: jnp.mean(g, axis=0), gathered)
            new_params, new_adam, opt_metrics = adamw_update(
                opt_cfg, g_mean, state.adam, params
            )
            is_server = (jax.lax.axis_index("pod") == 0).astype(jnp.float32)
            new_params = jax.tree.map(
                lambda u: jax.lax.psum(u * is_server.astype(u.dtype), "pod"), new_params
            )

        if multi_pod and strategy == "local_sgd":
            def do_outer(operands):
                p, d = operands
                return outer_step(diloco_cfg, p, d)

            new_params, new_diloco = jax.lax.cond(
                new_adam.step % diloco_cfg.sync_every == 0,
                do_outer,
                lambda operands: operands,
                (new_params, new_diloco),
            )

        metrics = dict(metrics)
        metrics["loss"] = loss
        metrics.update(opt_metrics)
        return new_params, TrainState(new_adam, new_ef, new_diloco), metrics

    # -- shardings -----------------------------------------------------------
    if params_shapes is None or batch_shapes is None:
        raise ValueError("params_shapes and batch_shapes are required")
    p_pspec = params_pspecs(params_shapes, mesh)
    b_pspec = batch_pspecs(batch_shapes, mesh)
    s_pspec = state_pspecs(params_shapes, mesh, strategy=strategy)
    if multi_pod:
        # jit-level batch placement is pod-only (the manual axis); "data"
        # spreading happens via the activation constraint inside.
        def _pod_only(spec: P) -> P:
            lead = spec[0] if len(spec) else None
            axes = lead if isinstance(lead, tuple) else (lead,)
            rest = [None] * max(len(spec) - 1, 0)
            return P("pod" if "pod" in axes else None, *rest)

        b_pspec = jax.tree.map(_pod_only, b_pspec, is_leaf=lambda x: isinstance(x, P))
    p_shard = _tree_shardings(p_pspec, mesh)
    b_shard = _tree_shardings(b_pspec, mesh)
    s_shard = _tree_shardings(s_pspec, mesh)

    if multi_pod:
        # pod axis is manual; everything else stays GSPMD-auto.
        def pod_batch_spec(spec: P) -> P:
            lead = spec[0] if len(spec) else None
            axes = lead if isinstance(lead, tuple) else (lead,)
            return P("pod" if "pod" in axes else None)

        in_specs = (
            jax.tree.map(lambda s: P(), p_pspec, is_leaf=lambda x: isinstance(x, P)),
            jax.tree.map(lambda s: P(), s_pspec, is_leaf=lambda x: isinstance(x, P)),
            jax.tree.map(pod_batch_spec, b_pspec, is_leaf=lambda x: isinstance(x, P)),
        )
        out_specs = (
            jax.tree.map(lambda s: P(), p_pspec, is_leaf=lambda x: isinstance(x, P)),
            jax.tree.map(lambda s: P(), s_pspec, is_leaf=lambda x: isinstance(x, P)),
            P(),
        )
        fn = jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            axis_names={"pod"},
            check_vma=False,
        )
    else:
        fn = inner

    jit_kwargs: Dict[str, Any] = dict(
        in_shardings=(p_shard, s_shard, b_shard),
        out_shardings=(p_shard, s_shard, None),
    )
    if donate:
        jit_kwargs["donate_argnums"] = (0, 1)
    step_fn = jax.jit(fn, **jit_kwargs)
    shardings = {"params": p_shard, "state": s_shard, "batch": b_shard}
    return step_fn, shardings


def make_prefill_step(cfg: ModelConfig, mesh: Mesh, *, params_shapes, batch_shapes):
    """Inference prefill: logits for the last position + KV caches."""
    p_shard = params_shardings(params_shapes, mesh)
    b_shard = batch_shardings(batch_shapes, mesh)

    def fn(params, batch):
        return prefill(params, batch, cfg)

    cache_shapes = jax.eval_shape(fn, params_shapes, batch_shapes)[1]
    c_shard = cache_shardings(cache_shapes, mesh)
    step_fn = jax.jit(
        fn,
        in_shardings=(p_shard, b_shard),
        out_shardings=(None, c_shard),
    )
    return step_fn, {"params": p_shard, "batch": b_shard, "cache": c_shard}


def make_decode_step(
    cfg: ModelConfig, mesh: Mesh, *, params_shapes, cache_shapes, token_shapes
):
    """One-token serve step against a seq_len-deep cache (decode shapes)."""
    p_shard = params_shardings(params_shapes, mesh)
    c_shard = cache_shardings(cache_shapes, mesh)
    t_shard = batch_shardings(token_shapes, mesh)

    def fn(params, tokens_t, cache, position):
        return model_decode_step(params, tokens_t, cache, cfg, position)

    step_fn = jax.jit(
        fn,
        in_shardings=(p_shard, t_shard, c_shard, None),
        out_shardings=(None, c_shard),
        donate_argnums=(2,),
    )
    return step_fn, {"params": p_shard, "cache": c_shard, "tokens": t_shard}
