"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, asserting output shapes and the absence of NaNs (assignment (f))."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, get_smoke_config
from repro.models import (
    IGNORE_LABEL,
    decode_step,
    forward,
    init_decode_cache,
    init_params,
    loss_fn,
    prefill,
)

B, S = 2, 16


def make_batch(cfg, key):
    kt, ke = jax.random.split(key)
    batch = {}
    if cfg.frontend == "frame":
        batch["frame_embeds"] = jax.random.normal(ke, (B, S, cfg.frontend_dim))
        batch["labels"] = jax.random.randint(kt, (B, S), 0, cfg.vocab_size)
    elif cfg.frontend == "patch":
        p = cfg.num_prefix_tokens
        batch["tokens"] = jax.random.randint(kt, (B, S - p), 0, cfg.vocab_size)
        batch["patch_embeds"] = jax.random.normal(ke, (B, p, cfg.frontend_dim))
        labels = np.full((B, S), IGNORE_LABEL, np.int32)
        labels[:, p:] = np.asarray(
            jax.random.randint(kt, (B, S - p), 0, cfg.vocab_size)
        )
        batch["labels"] = jnp.asarray(labels)
    else:
        batch["tokens"] = jax.random.randint(kt, (B, S), 0, cfg.vocab_size)
        batch["labels"] = batch["tokens"]
    return batch


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    batch = make_batch(cfg, key)
    logits, aux = jax.jit(lambda p, b: forward(p, b, cfg))(params, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all()), f"{arch}: NaN/inf in logits"
    assert bool(jnp.isfinite(aux)), f"{arch}: NaN/inf in aux loss"


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_train_step_finite_grads(arch):
    """One SGD step: loss and every gradient leaf finite; params update."""
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(1)
    params = init_params(key, cfg)
    batch = make_batch(cfg, key)

    @jax.jit
    def step(p, b):
        (loss, metrics), grads = jax.value_and_grad(
            lambda q: loss_fn(q, b, cfg), has_aux=True
        )(p)
        new_p = jax.tree.map(lambda a, g: a - 1e-3 * g.astype(a.dtype), p, grads)
        return loss, grads, new_p

    loss, grads, new_params = step(params, batch)
    assert bool(jnp.isfinite(loss)), f"{arch}: non-finite loss"
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
        assert bool(jnp.isfinite(leaf).all()), f"{arch}: non-finite grad at {path}"
    # at least the embedding moved
    moved = jax.tree.leaves(
        jax.tree.map(lambda a, b_: float(jnp.abs(a - b_).max()), params, new_params)
    )
    assert max(moved) > 0


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_prefill_decode_roundtrip(arch):
    """Greedy decode from a prefilled cache matches teacher-forced logits."""
    cfg = get_smoke_config(arch)
    if cfg.moe is not None and cfg.moe.impl != "held":
        # capacity drops make MoE decode diverge from batched forward by
        # design; covered with high capacity in tests/test_models.py (the
        # held-expert layer is dropless, so it runs here)
        pytest.skip("MoE capacity drops: covered separately")
    key = jax.random.PRNGKey(2)
    params = init_params(key, cfg)
    batch = make_batch(cfg, key)
    logits_full, _ = forward(params, batch, cfg)
    if cfg.frontend == "frame":
        n0 = S - 2
        pre = {"frame_embeds": batch["frame_embeds"][:, :n0], "labels": batch["labels"][:, :n0]}
        last, cache = prefill(params, pre, cfg, max_len=S)
        np.testing.assert_allclose(
            np.asarray(last), np.asarray(logits_full[:, n0 - 1]), rtol=3e-2, atol=3e-2
        )
        for t in range(n0, S):
            step_in = batch["frame_embeds"][:, t : t + 1]
            lt, cache = decode_step(params, step_in, cache, cfg, jnp.int32(t))
            np.testing.assert_allclose(
                np.asarray(lt), np.asarray(logits_full[:, t]), rtol=3e-2, atol=3e-2
            )
    elif cfg.frontend == "patch":
        # decode over the text region only
        last, cache = prefill(params, batch, cfg, max_len=S + 4)
        np.testing.assert_allclose(
            np.asarray(last), np.asarray(logits_full[:, -1]), rtol=3e-2, atol=3e-2
        )
    else:
        n0 = S - 4
        pre = {"tokens": batch["tokens"][:, :n0]}
        last, cache = prefill(params, pre, cfg, max_len=S)
        np.testing.assert_allclose(
            np.asarray(last), np.asarray(logits_full[:, n0 - 1]), rtol=3e-2, atol=3e-2
        )
        for t in range(n0, S):
            lt, cache = decode_step(params, batch["tokens"][:, t], cache, cfg, jnp.int32(t))
            np.testing.assert_allclose(
                np.asarray(lt), np.asarray(logits_full[:, t]), rtol=3e-2, atol=3e-2,
                err_msg=f"{arch} t={t}",
            )


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_decode_cache_structure(arch):
    """init_decode_cache matches prefill's cache pytree structure."""
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(3)
    params = init_params(key, cfg)
    batch = make_batch(cfg, key)
    if cfg.frontend == "patch":
        pytest.skip("prefix cache length differs by num_prefix_tokens")
    _, cache = prefill(params, batch, cfg, max_len=S)
    fresh = init_decode_cache(cfg, B, S)
    assert jax.tree.structure(cache) == jax.tree.structure(fresh)
    for a, b_ in zip(jax.tree.leaves(cache), jax.tree.leaves(fresh)):
        assert a.shape == b_.shape, f"{arch}: {a.shape} vs {b_.shape}"
