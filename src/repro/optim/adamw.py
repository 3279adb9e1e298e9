"""AdamW implemented natively on pytrees (no optax dependency).

Supports: global-norm clipping, decoupled weight decay, fp32 moments over
bf16 params, linear-warmup + cosine schedules, and donation-friendly
update signatures for the distributed step builders.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: Optional[float] = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    schedule: str = "cosine"  # cosine | constant


class AdamWState(NamedTuple):
    step: jnp.ndarray  # i32 scalar
    m: Any  # fp32 pytree
    v: Any  # fp32 pytree


def init_adamw(params) -> AdamWState:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return AdamWState(
        step=jnp.zeros((), jnp.int32),
        m=jax.tree.map(zeros, params),
        v=jax.tree.map(zeros, params),
    )


def schedule_lr(cfg: AdamWConfig, step) -> jnp.ndarray:
    step_f = step.astype(jnp.float32)
    warm = jnp.minimum(step_f / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    if cfg.schedule == "cosine":
        frac = jnp.clip(
            (step_f - cfg.warmup_steps) / max(cfg.total_steps - cfg.warmup_steps, 1),
            0.0, 1.0,
        )
        decay = 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    else:
        decay = 1.0
    return cfg.lr * warm * decay


def global_norm(tree) -> jnp.ndarray:
    sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(tree))
    return jnp.sqrt(sq)


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: g * scale.astype(g.dtype), grads), norm


def adamw_update(
    cfg: AdamWConfig, grads, state: AdamWState, params
) -> Tuple[Any, AdamWState, Dict[str, jnp.ndarray]]:
    """One AdamW step. Returns (new_params, new_state, metrics)."""
    with jax.named_scope("adamw"):
        metrics: Dict[str, jnp.ndarray] = {}
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        if cfg.clip_norm is not None:
            grads, norm = clip_by_global_norm(grads, cfg.clip_norm)
            metrics["grad_norm"] = norm
        step = state.step + 1
        lr = schedule_lr(cfg, step)
        metrics["lr"] = lr
        b1c = 1.0 - cfg.b1 ** step.astype(jnp.float32)
        b2c = 1.0 - cfg.b2 ** step.astype(jnp.float32)

        def upd(p, g, m, v):
            m = cfg.b1 * m + (1 - cfg.b1) * g
            v = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g)
            mhat = m / b1c
            vhat = v / b2c
            delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
            if cfg.weight_decay:
                delta = delta + cfg.weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state.m)
        flat_v = treedef.flatten_up_to(state.v)
        out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
        new_p = jax.tree.unflatten(treedef, [o[0] for o in out])
        new_m = jax.tree.unflatten(treedef, [o[1] for o in out])
        new_v = jax.tree.unflatten(treedef, [o[2] for o in out])
        return new_p, AdamWState(step=step, m=new_m, v=new_v), metrics
