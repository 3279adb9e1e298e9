"""Everything a run feeds the program, made from ``--seed``.

* Weights, in the layout of the program's parameter tree (its shapes only,
  from ``jax.eval_shape``), drawn on the device in one jitted call and
  stored in the configuration's ``param_dtype``.  The same call, with the
  same key, remakes them bit for bit for the reference.
* Token batches for training: a pool of distinct batches, uniform over the
  vocabulary, with next-token labels.
* Prompts for serving: batches of equal shape, each with contents of its
  own, from a stream for warm-up and one for the window.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def seed_words(seed: int) -> np.ndarray:
    """Two 32-bit words from a seed of any size (large seeds are fine)."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def jax_key(seed: int):
    """The key the weights are drawn with, from the seed's words."""
    import jax
    import jax.numpy as jnp

    return jax.random.fold_in(jax.random.wrap_key_data(jnp.asarray(seed_words(seed), jnp.uint32)), 1)


def leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _init_leaf(key, name: str, shape, dtype):
    import jax
    import jax.numpy as jnp

    last = name.rsplit("/", 1)[-1]
    if last == "embed":
        x = 0.02 * jax.random.normal(key, shape)
    elif last.startswith("w"):
        fan_in = shape[-2]
        x = jax.random.truncated_normal(key, -2.0, 2.0, shape) / np.sqrt(fan_in)
    elif last == "scale":
        x = 1.0 + 0.1 * jax.random.normal(key, shape)
    else:  # biases
        x = 0.02 * jax.random.normal(key, shape)
    return x.astype(dtype)


def weights_builder(param_shapes, out_shardings=None):
    """``seed -> weights`` for ``param_shapes`` (a tree of ShapeDtypeStructs):
    one jitted call on the device, bit for bit the same on every call."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(param_shapes)

    def build(key):
        leaves = [
            _init_leaf(jax.random.fold_in(key, i), leaf_name(path), s.shape, s.dtype)
            for i, (path, s) in enumerate(flat)
        ]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    fn = jax.jit(build, out_shardings=out_shardings)
    return lambda seed: fn(jax_key(seed))


def train_pool(seed: int, vocab: int, pool: int, batch: int, seq: int) -> List[Dict[str, np.ndarray]]:
    """``pool`` distinct batches of next-token pairs."""
    rng = np.random.default_rng(seed_words(seed))
    toks = rng.integers(0, vocab, size=(pool, batch, seq + 1), dtype=np.int32)
    return [{"tokens": t[:, :-1].copy(), "labels": t[:, 1:].copy()} for t in toks]


STREAMS = {"warmup": 0, "window": 1}


def serve_batch(seed: int, stream: str, index: int, batch: int, prompt_len: int, vocab: int) -> np.ndarray:
    """Batch ``index`` of ``stream``: ``batch`` prompts of ``prompt_len`` tokens."""
    seq = np.random.SeedSequence(seed_words(seed), spawn_key=(STREAMS[stream], index))
    return np.random.default_rng(seq).integers(0, vocab, size=(batch, prompt_len), dtype=np.int32)
