"""Plain reference: the decoder, its loss, AdamW and the int8 pod exchange.

Written from the configuration file alone, in float32 with every matmul at
``Precision.HIGHEST``; it imports nothing of the program.  It reads the
weights in the layout the benchmark made them (``inputs.weights_builder``):
``embed [V, D]``, ``final_norm``, and per-layer leaves stacked on a
leading layer axis under ``groups/slot0``.

The architecture, as the configurations state it:

* pre-norm decoder blocks; LayerNorm (eps 1e-5) with scale and bias, or
  OLMo's non-parametric LayerNorm;
* rotary positions over each head (theta 10000, the two halves of the
  head rotated against each other), scale ``head_dim ** -0.5``, causal;
* GELU (tanh form, GPT-2's ``gelu_new``) or SwiGLU feed-forward;
* tied unembedding; loss = mean next-token cross-entropy
  + ``z_loss * mean(logsumexp(logits) ** 2)``.

``precision="fp8"`` is the control: every matmul operand of the forward
pass is rounded to float8 e4m3 with a per-tensor absmax scale before an f32
product; gradients flow through the rounding unchanged.
"""

from __future__ import annotations

import json
import math
from typing import Dict, List, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .inputs import leaf_name

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _round(x, precision: str):
    """The matmul operand at ``precision``; gradients pass straight through
    in float32 (a float8 cast would carry the cotangents unscaled)."""
    if precision == "f32":
        return x
    scale = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX)
    low = (x / scale).astype(F8).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(low - x)


def _einsum(spec: str, a, b, precision: str):
    return jnp.einsum(spec, _round(a, precision), _round(b, precision), precision=HIGHEST)


def _norm(p: Mapping, x, m: Mapping):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    y = (x - mean) / jnp.sqrt(var + 1e-5)
    if m["norm"] == "layernorm":
        y = y * p["scale"] + p["bias"]
    elif m["norm"] != "nonparametric_ln":
        raise ValueError(f"reference has no norm {m['norm']!r}")
    return y


def _rope(x, theta: float):
    """x: [B, S, H, hd]; the first and second halves of hd rotate together."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x**3)))


def _layer(p: Mapping, x, m: Mapping, precision: str):
    B, S, D = x.shape
    H, KVH = m["num_heads"], m["num_kv_heads"]
    hd = m.get("head_dim") or D // H
    bias = m.get("use_bias_attn", False)
    h = _norm(p.get("norm1", {}), x, m)
    a = p["attn"]
    q = _einsum("bsd,de->bse", h, a["wq"], precision) + (a["bq"] if bias else 0.0)
    k = _einsum("bsd,de->bse", h, a["wk"], precision) + (a["bk"] if bias else 0.0)
    v = _einsum("bsd,de->bse", h, a["wv"], precision) + (a["bv"] if bias else 0.0)
    q = _rope(q.reshape(B, S, H, hd), m["rope_theta"])
    k = _rope(k.reshape(B, S, KVH, hd), m["rope_theta"])
    v = v.reshape(B, S, KVH, hd)
    rep = H // KVH
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = _einsum("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(hd)
    pos = jnp.arange(S)
    s = jnp.where((pos[:, None] >= pos[None, :])[None, None], s, -jnp.inf)
    probs = jax.nn.softmax(s, axis=-1)
    o = _einsum("bhqk,bkhd->bqhd", probs, v, precision).reshape(B, S, H * hd)
    x = x + _einsum("bse,ed->bsd", o, a["wo"], precision) + (a["bo"] if bias else 0.0)
    h = _norm(p.get("norm2", {}), x, m)
    f = p["ffn"]
    if m["activation"] == "gelu":
        u = _einsum("bsd,df->bsf", h, f["w_up"], precision)
        if m.get("use_bias_mlp"):
            u = u + f["b_up"]
        u = _gelu_tanh(u)
    elif m["activation"] == "swiglu":
        g = _einsum("bsd,df->bsf", h, f["w_gate"], precision)
        u = jax.nn.sigmoid(g) * g * _einsum("bsd,df->bsf", h, f["w_up"], precision)
    else:
        raise ValueError(f"reference has no activation {m['activation']!r}")
    y = _einsum("bsf,fd->bsd", u, f["w_down"], precision)
    if m.get("use_bias_mlp"):
        y = y + f["b_down"]
    return x + y


def logits(params: Mapping, tokens, m: Mapping, precision: str = "f32"):
    """[B, S] tokens -> [B, S, V] float32 logits."""
    if tuple(m["pattern"]) != ("attn",) or not m.get("tie_embeddings") or m.get("moe"):
        raise ValueError("the reference covers dense, tied, attention-only decoders")
    x = params["embed"].astype(jnp.float32)[tokens]
    layers = jax.tree.map(lambda a: a.astype(jnp.float32), params["groups"]["slot0"])
    layer = jax.checkpoint(lambda x, p: (_layer(p, x, m, precision), None))
    x, _ = jax.lax.scan(layer, x, layers)
    h = _norm(params.get("final_norm", {}), x, m)
    return _einsum("bsd,vd->bsv", h, params["embed"].astype(jnp.float32), precision)


# -- loss and gradient, in blocks of rows -------------------------------------------


def _block_terms(params, tokens, labels, m: Mapping, precision: str):
    lg = logits(params, tokens, m, precision)
    logz = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0] - logz
    return -ll.sum(), jnp.square(logz).sum()


_GRAD_FNS: Dict[str, object] = {}


def _block_grad(m: Mapping, precision: str, n_tokens: int):
    key = json.dumps([m, precision, n_tokens], sort_keys=True)
    if key not in _GRAD_FNS:
        def objective(params, tokens, labels):
            ce, zz = _block_terms(params, tokens, labels, m, precision)
            return ce / n_tokens + m["z_loss"] * zz / n_tokens, (ce, zz)

        _GRAD_FNS[key] = jax.jit(jax.grad(objective, has_aux=True))
    return _GRAD_FNS[key]


def loss_and_grad(params, tokens: np.ndarray, labels: np.ndarray, m: Mapping,
                  precision: str, rows_per_block: int, devices: Sequence = ()):
    """Mean loss and its gradient over all rows, accumulated block by block;
    with several ``devices``, each block holds ``rows_per_block`` rows per device."""
    n_tokens = tokens.size
    step = _block_grad(m, precision, n_tokens)
    width, put = rows_per_block, lambda a: a
    if len(devices) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        mesh = Mesh(np.asarray(devices), ("rows",))
        params = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
        width *= len(devices)
        put = lambda a: jax.device_put(a, NamedSharding(mesh, PartitionSpec("rows")))  # noqa: E731
    grads, ce, zz = None, 0.0, 0.0
    for r in range(0, tokens.shape[0], width):
        g, (c, z) = step(params, put(tokens[r : r + width]), put(labels[r : r + width]))
        grads = g if grads is None else tree_add(grads, g)
        ce, zz = ce + float(c), zz + float(z)
    return ce / n_tokens + m["z_loss"] * zz / n_tokens, grads


# -- the pod exchange and AdamW -------------------------------------------------------


def int8_round_trip(x, block: int = 256):
    """Per-block absmax int8 over the last axis, then back to float32."""
    shape = x.shape
    x2 = x.reshape(1) if x.ndim == 0 else x
    last = x2.shape[-1]
    pad = (-last) % block
    if pad:
        x2 = jnp.pad(x2, [(0, 0)] * (x2.ndim - 1) + [(0, pad)])
    b = x2.reshape(*x2.shape[:-1], -1, block)
    amax = jnp.max(jnp.abs(b), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(b / scale), -127, 127)
    return (q * scale).reshape(x2.shape)[..., :last].reshape(shape)


round_trip_tree = jax.jit(lambda t: jax.tree.map(int8_round_trip, t))
tree_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
tree_sub = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))
tree_mean = jax.jit(lambda trees: jax.tree.map(lambda *xs: sum(xs) / len(xs), *trees))
_ADAMW: Dict[str, object] = {}


def _adamw(opt: Mapping, grads, m, v, params, step):
    leaves = jax.tree.leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(norm, 1e-9))
    g = jax.tree.map(lambda x: x * scale, grads)
    warm = jnp.minimum(step / max(opt["warmup_steps"], 1), 1.0)
    frac = jnp.clip((step - opt["warmup_steps"]) / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0, 1.0)
    lr = opt["lr"] * warm * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
    c1, c2 = 1 - b1**step, 1 - b2**step
    params = jax.tree.map(
        lambda p, a, s: p - lr * ((a / c1) / (jnp.sqrt(s / c2) + opt["eps"]) + opt["weight_decay"] * p),
        params, m, v,
    )
    return params, m, v, g


def adamw(opt: Mapping, grads, m, v, params, step: int):
    """One AdamW step (cosine schedule after linear warm-up, global-norm
    clipping, decoupled decay on every leaf); returns (params, m, v, clipped grads)."""
    if opt.get("schedule", "cosine") != "cosine":
        raise ValueError("the reference follows the cosine schedule only")
    key = json.dumps(opt, sort_keys=True)
    if key not in _ADAMW:
        _ADAMW[key] = jax.jit(lambda *a: _adamw(opt, *a), donate_argnums=(0, 1, 2, 3))
    return _ADAMW[key](grads, m, v, params, jnp.float32(step))


def train_steps(params, batches: Sequence[Mapping[str, np.ndarray]], m: Mapping, opt: Mapping, *,
                strategy: str, pods: int, precision: str, rows_per_block: int, devices: Sequence = ()):
    """Follow the first ``len(batches)`` steps.  Returns per-step losses,
    the per-leaf norms of the first step's clipped gradient and the final
    parameters.  Buffers are donated from step to step, so that the
    reference holds one copy of the parameters, each moment and the
    gradient."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    mom, vel = zeros(params), zeros(params)
    ef = [zeros(params) for _ in range(pods)]
    losses: List[float] = []
    first_grad = None
    for k, batch in enumerate(batches, start=1):
        rows = batch["tokens"].shape[0] // pods
        pod_loss, pod_grads = [], []
        for p in range(pods):
            sl = slice(p * rows, (p + 1) * rows)
            loss, g = loss_and_grad(params, batch["tokens"][sl], batch["labels"][sl], m,
                                    precision, rows_per_block, devices)
            pod_loss.append(loss)
            pod_grads.append(g)
        losses.append(sum(pod_loss) / pods)
        if strategy == "hier_int8" and pods > 1:
            boosted = [tree_add(g, e) for g, e in zip(pod_grads, ef)]
            sent = [round_trip_tree(b) for b in boosted]
            ef = [tree_sub(b, s) for b, s in zip(boosted, sent)]
            grads = tree_mean(sent)
        else:
            grads = tree_mean(pod_grads)
        params, mom, vel, clipped = adamw(opt, grads, mom, vel, params, k)
        if first_grad is None:
            first_grad = leaf_norms(clipped)
        del clipped
    return losses, first_grad, params


# -- per-leaf norms --------------------------------------------------------------------


def _leaf_norms(tree) -> Dict[str, jnp.ndarray]:
    """Float32 norm of every leaf; leaves stacked over layers are split per layer."""
    out: Dict[str, jnp.ndarray] = {}
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = leaf_name(path)
        a = a.astype(jnp.float32)
        if name.startswith("groups/"):
            per = jnp.sqrt(jnp.sum(jnp.square(a.reshape(a.shape[0], -1)), axis=1))
            for i in range(a.shape[0]):
                out[f"{name}[{i}]"] = per[i]
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(a)))
    return out


leaf_norms = jax.jit(_leaf_norms)


@jax.jit
def change_norms(new, old) -> Dict[str, jnp.ndarray]:
    return _leaf_norms(jax.tree.map(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), new, old))


def to_host(norms: Mapping[str, jnp.ndarray]) -> Dict[str, float]:
    return {k: float(v) for k, v in jax.device_get(dict(norms)).items()}


# -- serving ------------------------------------------------------------------------------


def served_gaps(params, prompts: np.ndarray, served: np.ndarray, m: Mapping, *,
                precision: str = "f32", rows_per_block: int = 1) -> Tuple[np.ndarray, np.ndarray]:
    """For each served token, how far its reference logit lies below the
    reference's best at that position.

    prompts [R, P], served [R, G].  Returns ``(gap, control_gap)``, each [R, G]:
    the gap of the served token, and (for ``precision != "f32"``) the gap of
    the token the lower precision puts first at the same position.
    """
    P = prompts.shape[1]
    seq = np.concatenate([prompts, served[:, :-1]], axis=1)

    @jax.jit
    def block(params, toks, picks):
        ref = logits(params, toks, m, "f32")[:, P - 1 :]
        best = ref.max(-1)
        gap = best - jnp.take_along_axis(ref, picks[..., None], axis=-1)[..., 0]
        if precision == "f32":
            return gap, jnp.zeros_like(gap)
        low = logits(params, toks, m, precision)[:, P - 1 :]
        top = jnp.argmax(low, axis=-1)
        return gap, best - jnp.take_along_axis(ref, top[..., None], axis=-1)[..., 0]

    gaps, ctrl = [], []
    for r in range(0, seq.shape[0], rows_per_block):
        g, c = block(params, seq[r : r + rows_per_block], served[r : r + rows_per_block])
        gaps.append(np.asarray(g))
        ctrl.append(np.asarray(c))
    return np.concatenate(gaps), np.concatenate(ctrl)
