"""Plain reference of Mellum2-12B-A2.5B's layers, whole or at the share of
them one chip holds (``held`` experts from ``first_held``), written from the
source configuration alone (huggingface.co/JetBrains/Mellum2-12B-A2.5B-
Instruct, config.json).  It imports nothing of ``repro``; ``m`` is the
model configuration as a mapping (``dataclasses.asdict(ModelConfig)``).

* Pre-norm blocks; RMSNorm, eps 1e-6 (the source's ``rms_norm_eps``), its
  weight the gain, as the source's checkpoint holds it; no biases, no q/k
  norm.
* Grouped-query attention: 32 query heads and 4 key/value heads of 128,
  each key and value head repeated over its 8 query heads; scale
  ``head_dim ** -0.5``.  The mask comes from positions: a ``local_attn``
  layer attends to the ``local_window`` positions up to its own, an
  ``attn`` layer to every earlier one.  Scores are computed for blocks of
  ``QUERY_BLOCK`` queries at a time, each block recomputed in the
  backward, so that 8,192 positions fit.
* Rotary positions, theta 500,000, the two halves of each head rotated
  against each other: YaRN's frequencies in the layer kinds that
  ``yarn["kinds"]`` names (``attn``, the full layers; Peng et al. 2023,
  arXiv 2309.00071; the ``yarn`` rope type of Hugging Face transformers),
  whose cos and sin are scaled by its attention factor, and the default
  frequencies in the others (``local_attn``, the sliding layers).
* In every layer a sparse SwiGLU MLP: a softmax router over all
  ``num_experts``, each token's top ``num_experts_per_tok`` with their
  weights renormalised over them.  Each expert held here (``held`` of
  them from ``first_held``) is applied to every token, weighted by the
  token's weight for it (zero where the token did not choose it), and the
  products are summed; experts held elsewhere add nothing.
* ``extra_loss``: each layer's Switch balance loss, ``num_experts * sum_e
  f_e P_e`` (``f_e`` the share of the rows' token choices that went to
  expert ``e``, ``P_e`` its mean router probability), summed over the
  layers, times ``router_aux_coef``.
* An untied head over the vocabulary's slice.

Weights are read in the program's layout: per-layer leaves stacked on a
leading group axis under ``groups/slot<i>``, the i-th kind of ``pattern``;
the held experts' weights ``[held, ...]``.  ``forward`` has the signature
of the benchmark's references (``benchmarks/chip/reference.py``), whose
``einsum`` gives every matmul its precision.
"""

import math

import jax
import jax.numpy as jnp

from benchmarks.chip.reference import einsum

QUERY_BLOCK = 256
EPS = 1e-6


def rms(p, x):
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + EPS) * p["scale"]


def inverse_frequencies(hd: int, theta: float, yarn=None):
    """Rotary inverse frequencies of the head's pairs, and the cos/sin scale."""
    base = theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    if yarn is None:
        return 1.0 / base, 1.0
    factor = yarn["factor"]

    def correction_dim(rotations):  # the pair index that turns ``rotations`` times
        return hd * math.log(yarn["original_max_position_embeddings"] / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(yarn.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(yarn.get("beta_slow", 1))), hd - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(hd // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    extrapolation = 1.0 - ramp  # the share of the original frequency
    inv = (1.0 / (factor * base)) * (1.0 - extrapolation) + (1.0 / base) * extrapolation
    scale = yarn.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return inv, scale


def rope(x, inv, scale):
    """x: [B, S, H, hd]; rotated by position."""
    hd = x.shape[-1]
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = (scale * f(ang)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    a, b = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(q, k, v, window, precision):
    """q: [B, S, H, hd], k and v: [B, S, H, hd]; causal, and within ``window``
    positions where it is given; in blocks of queries."""
    B, S, H, hd = q.shape
    qb = min(QUERY_BLOCK, S)
    pos = jnp.arange(S)

    @jax.checkpoint
    def block(start):
        qi = jax.lax.dynamic_slice_in_dim(q, start, qb, axis=1)
        qpos = start + jnp.arange(qb)
        s = einsum("bqhd,bkhd->bhqk", qi, k, precision) / math.sqrt(hd)
        mask = pos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= pos[None, :] > qpos[:, None] - window
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
        return einsum("bhqk,bkhd->bqhd", p, v, precision)

    out = jax.lax.map(block, jnp.arange(0, S, qb))  # [S / qb, B, qb, H, hd]
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H * hd)


def sparse_mlp(p, h, m, precision):
    """Returns (the held experts' share of the layer's output, balance loss)."""
    moe = m["moe"]
    E, k = moe["num_experts"], moe["num_experts_per_tok"]
    first, held = moe.get("first_held", 0), moe.get("held") or E
    B, S, D = h.shape
    x = h.reshape(B * S, D)
    probs = jax.nn.softmax(einsum("td,de->te", x, p["router"], precision), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    top = top / top.sum(-1, keepdims=True)
    chosen = jax.nn.one_hot(idx, E)  # [T, k, E]
    weight = jnp.einsum("tk,tke->te", top, chosen)[:, first : first + held]  # [T, held]
    g = einsum("td,edf->etf", x, p["w_gate"], precision)
    u = jax.nn.sigmoid(g) * g * einsum("td,edf->etf", x, p["w_up"], precision)
    y = einsum("etf,efd->etd", u * weight.T[:, :, None], p["w_down"], precision).sum(0)
    share = chosen.sum((0, 1)) / (B * S * k)
    balance = E * jnp.sum(share * probs.mean(0))
    return y.reshape(B, S, D), balance


def layer(p, x, kind, m, precision):
    B, S, D = x.shape
    H, KVH, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    window = m["local_window"] if kind == "local_attn" else m.get("window")
    yarn = m.get("yarn")
    inv, scale = inverse_frequencies(hd, m["rope_theta"], yarn if yarn and kind in yarn["kinds"] else None)
    h, a = rms(p["norm1"], x), p["attn"]
    q = rope(einsum("bsd,de->bse", h, a["wq"], precision).reshape(B, S, H, hd), inv, scale)
    k = rope(einsum("bsd,de->bse", h, a["wk"], precision).reshape(B, S, KVH, hd), inv, scale)
    v = einsum("bsd,de->bse", h, a["wv"], precision).reshape(B, S, KVH, hd)
    k, v = jnp.repeat(k, H // KVH, axis=2), jnp.repeat(v, H // KVH, axis=2)
    x = x + einsum("bse,ed->bsd", attention(q, k, v, window, precision), a["wo"], precision)
    y, balance = sparse_mlp(p["ffn"], rms(p["norm2"], x), m, precision)
    return x + y, balance


def forward(params, tokens, m, precision):
    """[B, S] tokens -> ([B, S, V] float32 logits, the balance loss term)."""
    if m["norm"] != "rmsnorm_gain" or m["activation"] != "swiglu" or m["tie_embeddings"]:
        raise ValueError("this reference covers Mellum2's block: RMSNorm, SwiGLU experts, untied head")
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)  # noqa: E731
    x = f32(params["embed"])[tokens]
    groups = f32(params["groups"])
    balance = 0.0
    for g in range(m["num_layers"] // len(m["pattern"])):
        for s, kind in enumerate(m["pattern"]):
            p = jax.tree.map(lambda a: a[g], groups[f"slot{s}"])
            x, b = jax.checkpoint(lambda p, x, kind=kind: layer(p, x, kind, m, precision))(p, x)
            balance = balance + b
    h = rms(f32(params["final_norm"]), x)
    logits = einsum("bsd,dv->bsv", h, f32(params["unembed"]), precision)
    return logits, m["moe"]["router_aux_coef"] * balance
