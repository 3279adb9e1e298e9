"""Plain reference: what every architecture shares, and the lookup of the
architecture a configuration runs.

A configuration's architecture is one function,

    forward(params, tokens, m, precision) -> (logits, extra_loss)

from ``[B, S]`` tokens to ``[B, S, V]`` float32 logits, written from the
configuration file's ``model`` block ``m`` alone and importing nothing of
the program.  ``extra_loss`` is the architecture's own term of the loss for
these rows (a mixture of experts' balance loss, with its coefficient), a
mean over their tokens, or ``0.0``.  ``lookup(config)`` takes it from
``references/<config name>.py`` where that file exists, and otherwise from
``dense.py``, the dense tied decoder.  Each reads the weights in the layout
the benchmark made them (``inputs.weights_builder``).

Shared by every architecture, here:

* the precision helpers: every matmul through ``einsum``, in float32 at
  ``Precision.HIGHEST``; ``precision="fp8"`` is the control, which rounds
  every matmul operand of the forward pass to float8 e4m3 with a
  per-tensor absmax scale before an f32 product (gradients flow through
  the rounding unchanged);
* the loss: mean next-token cross-entropy
  + ``z_loss * mean(logsumexp(logits) ** 2)`` + the extra term, and its
  gradient, accumulated over blocks of rows (an extra term is weighted by
  its block's share of the tokens);
* the pod exchange of each strategy, with the int8 round trip and error
  feedback of ``hier_int8``; AdamW; per-leaf norms and changes;
* ``served_gaps``, which compares served tokens with the forward pass.
"""

from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .inputs import leaf_name

#: Where a configuration's own architecture lives, as ``<config name>.py``.
REFERENCES = Path(__file__).resolve().parent / "references"

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


def einsum(spec: str, a, b, precision: str):
    return jnp.einsum(spec, _round(a, precision), _round(b, precision), precision=HIGHEST)


# -- the architecture ------------------------------------------------------------------

Forward = Callable[[Mapping, jnp.ndarray, Mapping, str], Tuple[jnp.ndarray, jnp.ndarray]]


@functools.lru_cache(maxsize=None)
def _forward_at(path: str) -> Forward:
    """``forward`` of the module at ``path``, loaded once per process."""
    spec = importlib.util.spec_from_file_location("bench_reference_" + Path(path).stem.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.forward


def lookup(config: str) -> "Reference":
    """The reference of configuration ``config``: its own architecture in
    ``references/<config>.py`` where that file exists, else the dense decoder."""
    path = REFERENCES / f"{config}.py"
    if path.is_file():
        return Reference(_forward_at(str(path)), str(path))
    from . import dense

    return Reference(dense.forward, "dense.py")


# -- loss and gradient, in blocks of rows -------------------------------------------

_GRAD_FNS: Dict[Tuple[Forward, str], object] = {}


def _block_grad(forward: Forward, m: Mapping, precision: str, n_tokens: int):
    key = (forward, json.dumps([m, precision, n_tokens], sort_keys=True))
    if key not in _GRAD_FNS:
        def objective(params, tokens, labels):
            lg, extra = forward(params, tokens, m, precision)
            logz = jax.nn.logsumexp(lg, axis=-1)
            ll = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0] - logz
            ce, zz = -ll.sum(), jnp.square(logz).sum()
            extra = extra * (tokens.size / n_tokens)
            return ce / n_tokens + m["z_loss"] * zz / n_tokens + extra, (ce, zz, extra)

        _GRAD_FNS[key] = jax.jit(jax.grad(objective, has_aux=True))
    return _GRAD_FNS[key]


class Reference:
    """The shared loss, training steps and served-token comparison, over one
    architecture's ``forward``; ``source`` says where that came from."""

    def __init__(self, forward: Forward, source: str):
        self.forward, self.source = forward, source

    def loss_and_grad(self, params, tokens: np.ndarray, labels: np.ndarray, m: Mapping,
                      precision: str, rows_per_block: int, devices: Sequence = ()):
        """Mean loss and its gradient over all rows, accumulated block by block;
        with several ``devices``, each block holds ``rows_per_block`` rows per device."""
        n_tokens = tokens.size
        step = _block_grad(self.forward, m, precision, n_tokens)
        width, put = rows_per_block, lambda a: a
        if len(devices) > 1:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec

            mesh = Mesh(np.asarray(devices), ("rows",))
            params = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
            width *= len(devices)
            put = lambda a: jax.device_put(a, NamedSharding(mesh, PartitionSpec("rows")))  # noqa: E731
        grads, ce, zz, extra = None, 0.0, 0.0, 0.0
        for r in range(0, tokens.shape[0], width):
            g, (c, z, x) = step(params, put(tokens[r : r + width]), put(labels[r : r + width]))
            grads = g if grads is None else tree_add(grads, g)
            ce, zz, extra = ce + float(c), zz + float(z), extra + float(x)
        return ce / n_tokens + m["z_loss"] * zz / n_tokens + extra, grads

    def train_steps(self, params, batches: Sequence[Mapping[str, np.ndarray]], m: Mapping, opt: Mapping, *,
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
                loss, g = self.loss_and_grad(params, batch["tokens"][sl], batch["labels"][sl], m,
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

    def served_gaps(self, params, prompts: np.ndarray, served: np.ndarray, m: Mapping, *,
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
            ref = self.forward(params, toks, m, "f32")[0][:, P - 1 :]
            best = ref.max(-1)
            gap = best - jnp.take_along_axis(ref, picks[..., None], axis=-1)[..., 0]
            if precision == "f32":
                return gap, jnp.zeros_like(gap)
            low = self.forward(params, toks, m, precision)[0][:, P - 1 :]
            top = jnp.argmax(low, axis=-1)
            return gap, best - jnp.take_along_axis(ref, top[..., None], axis=-1)[..., 0]

        gaps, ctrl = [], []
        for r in range(0, seq.shape[0], rows_per_block):
            g, c = block(params, seq[r : r + rows_per_block], served[r : r + rows_per_block])
            gaps.append(np.asarray(g))
            ctrl.append(np.asarray(c))
        return np.concatenate(gaps), np.concatenate(ctrl)


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
