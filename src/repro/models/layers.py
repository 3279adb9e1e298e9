"""Shared building blocks: norms, rotary embeddings, initializers."""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from .config import ModelConfig, YarnConfig


# -- initializers -------------------------------------------------------------


def dense_init(key, shape, in_axis_size: Optional[int] = None, dtype=jnp.float32):
    """Truncated-normal fan-in init (1/sqrt(fan_in))."""
    fan_in = in_axis_size if in_axis_size is not None else shape[0]
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape) * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# -- norms ---------------------------------------------------------------------


def rms_norm(x, scale, eps: float = 1e-6, offset: float = 1.0):
    """RMSNorm with f32 *reduction* but compute-dtype elementwise math; the
    gain is ``offset + scale`` (``offset`` 0 where ``scale`` is the gain).

    Upcasting the whole activation to f32 (the naive formulation) makes
    XLA materialize — and, under sequence parallelism, ALL-GATHER — f32
    copies of every (B, S, D) tensor, doubling collective and HBM traffic
    (measured on arctic-480b, §Perf iteration 3).  Only the variance
    reduction needs f32; the scaling multiply stays in bf16.
    """
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    out = x * inv
    if scale is not None:
        out = out * (offset + scale).astype(x.dtype)
    return out


def layer_norm(x, scale, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    out = (x - mean.astype(x.dtype)) * inv
    if scale is not None:
        out = out * scale.astype(x.dtype)
    if bias is not None:
        out = out + bias.astype(x.dtype)
    return out


def nonparametric_ln(x, eps: float = 1e-5):
    """OLMo's non-parametric LayerNorm: no learnable scale or bias."""
    return layer_norm(x, None, None, eps=eps)


def init_norm(key, cfg: ModelConfig):
    if cfg.norm == "nonparametric_ln":
        return {}
    if cfg.norm == "layernorm":
        return {
            "scale": jnp.ones((cfg.d_model,), jnp.float32),
            "bias": jnp.zeros((cfg.d_model,), jnp.float32),
        }
    if cfg.norm == "rmsnorm_gain":  # the gain itself, as Llama-family checkpoints hold it
        return {"scale": jnp.ones((cfg.d_model,), jnp.float32)}
    # rmsnorm: stored as (scale - 1) so zeros-init is identity
    return {"scale": jnp.zeros((cfg.d_model,), jnp.float32)}


def apply_norm(params, x, cfg: ModelConfig):
    if cfg.norm == "nonparametric_ln":
        return nonparametric_ln(x)
    if cfg.norm == "layernorm":
        return layer_norm(x, params["scale"], params["bias"])
    if cfg.norm == "rmsnorm_gain":
        return rms_norm(x, params["scale"], offset=0.0)
    return rms_norm(x, params["scale"])


# -- rotary position embeddings --------------------------------------------------


def rope_frequencies(head_dim: int, fraction: float, theta: float):
    """Inverse frequencies for the rotated sub-dimension."""
    rot_dim = int(head_dim * fraction)
    rot_dim -= rot_dim % 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim))
    return inv, rot_dim


def yarn_frequencies(head_dim: int, fraction: float, theta: float, yarn: YarnConfig):
    """YaRN's inverse frequencies and attention factor (``YarnConfig``).

    Dimension pair ``i`` turns ``L / (2 pi) * inv[i]`` times over the original
    context ``L``: pairs that turn more than ``beta_fast`` times keep their
    frequency, those under ``beta_slow`` are interpolated by ``factor``, and a
    linear ramp over the pair index joins the two, its ends rounded outwards.
    """
    inv, rot_dim = rope_frequencies(head_dim, fraction, theta)

    def pair_turning(turns: float) -> float:  # the pair that turns ``turns`` times
        orig = yarn.original_max_position_embeddings
        return rot_dim * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_turning(yarn.beta_fast)), 0)
    high = min(math.ceil(pair_turning(yarn.beta_slow)), rot_dim - 1)
    ramp = jnp.clip(
        (jnp.arange(rot_dim // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0
    )
    inv = inv / yarn.factor * ramp + inv * (1.0 - ramp)
    factor = yarn.attention_factor
    if factor is None:
        factor = 0.1 * math.log(yarn.factor) + 1.0 if yarn.factor > 1 else 1.0
    return inv, rot_dim, factor


def apply_rope(
    x, positions, *, theta: float = 10_000.0, fraction: float = 1.0,
    yarn: Optional[YarnConfig] = None,
):
    """Rotary embedding over the leading ``fraction`` of the head dim.

    ``fraction=1.0`` is standard (llama/starcoder); ``fraction=0.5`` is the
    ChatGLM "2d" convention where only half of each head rotates and the
    other half carries position-free content.  With ``yarn`` the
    frequencies and the cos/sin scale are YaRN's (:func:`yarn_frequencies`).

    x: [..., seq, heads, head_dim]; positions: [..., seq]
    """
    head_dim = x.shape[-1]
    if yarn is None:
        inv, rot_dim = rope_frequencies(head_dim, fraction, theta)
        scale = None
    else:
        inv, rot_dim, scale = yarn_frequencies(head_dim, fraction, theta, yarn)
    if rot_dim == 0:
        return x
    x_rot, x_pass = x[..., :rot_dim], x[..., rot_dim:]
    angles = positions[..., None].astype(jnp.float32) * inv  # [..., seq, rot/2]
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([rotated.astype(x.dtype), x_pass], axis=-1)


# -- activations -----------------------------------------------------------------


def activation_fn(name: str):
    if name == "gelu":
        return jax.nn.gelu
    if name == "relu_sq":
        return lambda x: jnp.square(jax.nn.relu(x))
    if name == "silu":
        return jax.nn.silu
    raise ValueError(f"not a simple activation: {name}")


def softcap(x, cap: Optional[float]):
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)
