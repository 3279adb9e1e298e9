"""Test-only reference of a configuration that the dense reference refuses:
RMSNorm (eps 1e-6, its weight stored as ``scale - 1``) and an untied head
``unembed [D, V]``; rotary causal attention and SwiGLU, no biases.

``test_chip_reference.py`` finds it by the configuration's name, as the
harness finds ``references/<config name>.py``, here and in a checkout it
copies it into.  Written from the configuration alone.
"""

import math

import jax
import jax.numpy as jnp

from benchmarks.chip.dense import rope
from benchmarks.chip.reference import einsum


def rms(p, x):
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + 1e-6) * (1.0 + p["scale"])


def block(p, x, m, precision):
    B, S, D = x.shape
    H, hd = m["num_heads"], m["head_dim"]
    h, a = rms(p["norm1"], x), p["attn"]
    q, k, v = (einsum("bsd,de->bse", h, a[w], precision).reshape(B, S, H, hd) for w in ("wq", "wk", "wv"))
    q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
    s = einsum("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -jnp.inf)
    o = einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, precision).reshape(B, S, H * hd)
    x = x + einsum("bse,ed->bsd", o, a["wo"], precision)
    h, f = rms(p["norm2"], x), p["ffn"]
    g = einsum("bsd,df->bsf", h, f["w_gate"], precision)
    u = jax.nn.sigmoid(g) * g * einsum("bsd,df->bsf", h, f["w_up"], precision)
    return x + einsum("bsf,fd->bsd", u, f["w_down"], precision)


def forward(params, tokens, m, precision):
    assert m["norm"] == "rmsnorm" and not m["tie_embeddings"] and m["num_kv_heads"] == m["num_heads"]
    x = params["embed"].astype(jnp.float32)[tokens]
    layers = jax.tree.map(lambda a: a.astype(jnp.float32), params["groups"]["slot0"])
    x, _ = jax.lax.scan(lambda x, p: (block(p, x, m, precision), None), x, layers)
    h = rms(params["final_norm"], x)
    return einsum("bsd,dv->bsv", h, params["unembed"].astype(jnp.float32), precision), 0.0
