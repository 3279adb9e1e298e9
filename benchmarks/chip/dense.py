"""The default architecture of the plain reference: a dense, tied decoder.

A configuration that brings no ``references/<config name>.py`` is run
through this ``forward``.  Written from the configuration file alone, in
float32 with every matmul at ``Precision.HIGHEST`` (``reference.einsum``);
it imports nothing of the program.  It reads the weights in the layout the
benchmark made them (``inputs.weights_builder``): ``embed [V, D]``,
``final_norm``, and per-layer leaves stacked on a leading layer axis under
``groups/slot0``.

The architecture, as the configurations state it:

* pre-norm decoder blocks; LayerNorm (eps 1e-5) with scale and bias, or
  OLMo's non-parametric LayerNorm;
* rotary positions over each head (theta 10000, the two halves of the
  head rotated against each other), scale ``head_dim ** -0.5``, causal;
* GELU (tanh form, GPT-2's ``gelu_new``) or SwiGLU feed-forward;
* tied unembedding, and no loss term beyond the shared cross-entropy and
  z-loss.
"""

from __future__ import annotations

import math
from typing import Mapping

import jax
import jax.numpy as jnp

from .reference import einsum


def norm(p: Mapping, x, m: Mapping):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    y = (x - mean) / jnp.sqrt(var + 1e-5)
    if m["norm"] == "layernorm":
        y = y * p["scale"] + p["bias"]
    elif m["norm"] != "nonparametric_ln":
        raise ValueError(f"reference has no norm {m['norm']!r}")
    return y


def rope(x, theta: float):
    """x: [B, S, H, hd]; the first and second halves of hd rotate together."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x**3)))


def layer(p: Mapping, x, m: Mapping, precision: str):
    B, S, D = x.shape
    H, KVH = m["num_heads"], m["num_kv_heads"]
    hd = m.get("head_dim") or D // H
    bias = m.get("use_bias_attn", False)
    h = norm(p.get("norm1", {}), x, m)
    a = p["attn"]
    q = einsum("bsd,de->bse", h, a["wq"], precision) + (a["bq"] if bias else 0.0)
    k = einsum("bsd,de->bse", h, a["wk"], precision) + (a["bk"] if bias else 0.0)
    v = einsum("bsd,de->bse", h, a["wv"], precision) + (a["bv"] if bias else 0.0)
    q = rope(q.reshape(B, S, H, hd), m["rope_theta"])
    k = rope(k.reshape(B, S, KVH, hd), m["rope_theta"])
    v = v.reshape(B, S, KVH, hd)
    rep = H // KVH
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = einsum("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(hd)
    pos = jnp.arange(S)
    s = jnp.where((pos[:, None] >= pos[None, :])[None, None], s, -jnp.inf)
    probs = jax.nn.softmax(s, axis=-1)
    o = einsum("bhqk,bkhd->bqhd", probs, v, precision).reshape(B, S, H * hd)
    x = x + einsum("bse,ed->bsd", o, a["wo"], precision) + (a["bo"] if bias else 0.0)
    h = norm(p.get("norm2", {}), x, m)
    f = p["ffn"]
    if m["activation"] == "gelu":
        u = einsum("bsd,df->bsf", h, f["w_up"], precision)
        if m.get("use_bias_mlp"):
            u = u + f["b_up"]
        u = gelu_tanh(u)
    elif m["activation"] == "swiglu":
        g = einsum("bsd,df->bsf", h, f["w_gate"], precision)
        u = jax.nn.sigmoid(g) * g * einsum("bsd,df->bsf", h, f["w_up"], precision)
    else:
        raise ValueError(f"reference has no activation {m['activation']!r}")
    y = einsum("bsf,fd->bsd", u, f["w_down"], precision)
    if m.get("use_bias_mlp"):
        y = y + f["b_down"]
    return x + y


def forward(params: Mapping, tokens, m: Mapping, precision: str):
    """[B, S] tokens -> ([B, S, V] float32 logits, no extra loss term)."""
    if tuple(m["pattern"]) != ("attn",) or not m.get("tie_embeddings") or m.get("moe"):
        raise ValueError("the dense reference covers dense, tied, attention-only decoders; "
                         "a configuration of another kind brings references/<config name>.py")
    x = params["embed"].astype(jnp.float32)[tokens]
    layers = jax.tree.map(lambda a: a.astype(jnp.float32), params["groups"]["slot0"])
    body = jax.checkpoint(lambda x, p: (layer(p, x, m, precision), None))
    x, _ = jax.lax.scan(body, x, layers)
    h = norm(params.get("final_norm", {}), x, m)
    return einsum("bsd,vd->bsv", h, params["embed"].astype(jnp.float32), precision), 0.0
