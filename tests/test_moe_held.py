"""The held-expert MoE layer (``MoEConfig(impl="held")``, ``models/ffn.py``).

The layer one chip of an expert-parallel deployment computes: the router
scores every expert, each token takes its top k with renormalised weights,
and every (token, expert) pair whose expert is held here is computed, with
no capacity.  Checked here on the CPU against a plain layer written in the
test: the dense sum over the held experts of each expert's output on every
token, weighted by the token's weight for it.

* the layer, its balance loss and its gradients equal the plain layer's;
* the shares of 8 chips, each holding 8 of 64 experts, add up to the uncut
  64-expert layer (the share test of the ``model-configs`` guide);
* with every token routed onto the held experts, the worst case of the
  pair buffer, every pair is computed and ``moe_pairs`` counts them all;
* the Pallas grouped matmul (interpret mode) computes what
  ``jax.lax.ragged_dot`` computes on the rows of the groups, forward and
  backward, and leaves the rows past the groups alone.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ModelConfig, MoEConfig, init_params, loss_fn
from repro.models.ffn import grouped_matmul_kernel, init_moe, moe_ffn

E, K, D, F = 64, 8, 32, 16


def _cfg(held=None, first=0, **kw):
    moe = MoEConfig(num_experts=E, num_experts_per_tok=K, impl="held", held=held, first_held=first,
                    router_aux_coef=0.001)
    return ModelConfig(name="held-test", family="moe", num_layers=1, d_model=D, num_heads=2, num_kv_heads=1,
                       d_ff=F, vocab_size=64, moe=moe, dtype="float32", **kw)


def _plain_layer(params, x, cfg):
    """Every held expert on every token, weighted by the token's renormalised
    top-k weight for it (zero where it was not chosen); and the Switch loss."""
    moe = cfg.moe
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    hi = jax.lax.Precision.HIGHEST
    probs = jax.nn.softmax(jnp.dot(xf, params["router"], precision=hi), axis=-1)
    top, idx = jax.lax.top_k(probs, moe.num_experts_per_tok)
    top = top / top.sum(-1, keepdims=True)
    chosen = jax.nn.one_hot(idx, moe.num_experts)
    weight = jnp.einsum("tk,tke->te", top, chosen)[:, moe.first_held : moe.first_held + moe.num_held]
    g = jnp.einsum("td,edf->etf", xf, params["w_gate"], precision=hi)
    u = jax.nn.silu(g) * jnp.einsum("td,edf->etf", xf, params["w_up"], precision=hi)
    y = jnp.einsum("etf,efd,te->td", u, params["w_down"], weight, precision=hi)
    f = chosen.sum((0, 1)) / (b * s * moe.num_experts_per_tok)
    return y.reshape(b, s, d), moe.num_experts * jnp.sum(f * probs.mean(0))


def _share(params, first, held):
    """The weights chip ``first // held`` holds: the router whole, its experts."""
    out = dict(params)
    for w in ("w_gate", "w_up", "w_down"):
        out[w] = params[w][first : first + held]
    return out


@pytest.fixture(scope="module")
def uncut():
    cfg = _cfg()
    params = init_moe(jax.random.PRNGKey(0), cfg)
    # a router as wide as the model's: choices spread over the experts
    params["router"] = 0.3 * params["router"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, D))
    return cfg, params, x


@pytest.mark.parametrize("first", [0, 24, 56])
def test_held_layer_matches_the_plain_layer(uncut, first):
    _, params0, x = uncut
    cfg = _cfg(held=8, first=first)
    params = _share(params0, first, 8)

    def run(fn):
        def loss(p, x):
            y, aux = fn(p, x)[:2]
            return jnp.sum(y * jnp.cos(x)) + aux, (y, aux)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(params, x)

    (_, (y, aux)), grads = run(lambda p, x: moe_ffn(p, x, cfg))
    (_, (y_ref, aux_ref)), grads_ref = run(lambda p, x: _plain_layer(p, x, cfg))
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(aux, aux_ref, rtol=1e-6)
    for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_ref)):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5)


def test_eight_shares_add_up_to_the_uncut_layer(uncut):
    cfg, params, x = uncut
    whole, aux = moe_ffn(params, x, cfg)[:2]
    plain, plain_aux = _plain_layer(params, x, cfg)
    np.testing.assert_allclose(whole, plain, rtol=1e-5, atol=1e-5)
    shares = [moe_ffn(_share(params, c * 8, 8), x, _cfg(held=8, first=c * 8)) for c in range(8)]
    np.testing.assert_allclose(sum(s[0] for s in shares), whole, rtol=1e-5, atol=1e-5)
    # the router, and so the balance loss, is what every chip computes alike
    for _, share_aux, _ in shares:
        np.testing.assert_allclose(share_aux, aux, rtol=1e-6)
    # every pair is computed once, on the chip that holds its expert
    t = x.shape[0] * x.shape[1]
    assert sum(int(s[2]) for s in shares) == t * K
    assert int(moe_ffn(params, x, cfg)[2]) == t * K
    assert all(int(s[2]) > 0 for s in shares)


def test_routing_onto_the_held_experts_drops_no_pair():
    cfg = _cfg(held=8, first=16)
    params = init_moe(jax.random.PRNGKey(2), _cfg())
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, D))
    x = x.at[..., 0].set(4.0)  # one feature that every token shares ...
    params["router"] = params["router"].at[0, 16:24].add(10.0)  # ... sends every token to experts 16-23
    y, _, pairs = moe_ffn(_share(params, 16, 8), x, cfg)
    assert int(pairs) == 2 * 32 * K  # all of them, the pair buffer's worst case
    np.testing.assert_allclose(y, _plain_layer(_share(params, 16, 8), x, cfg)[0], rtol=1e-5, atol=1e-5)
    # a capacity would have dropped most: the GShard form keeps 1.25 * T * k / E per expert
    einsum = dataclasses.replace(cfg, moe=MoEConfig(num_experts=E, num_experts_per_tok=K, impl="einsum"))
    dropped = moe_ffn(params, x, einsum)[0]
    full = moe_ffn(params, x, _cfg())[0]
    assert float(jnp.abs(dropped - full).max()) > 0.1


def test_moe_pairs_is_in_the_step_metrics():
    cfg = dataclasses.replace(_cfg(held=8, first=8), num_layers=2)
    params = init_params(jax.random.PRNGKey(4), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 16), 0, cfg.vocab_size)
    _, metrics = jax.jit(lambda p, b: loss_fn(p, b, cfg))(params, {"tokens": tokens, "labels": tokens})
    # 2 layers x 32 tokens x 8 choices, an eighth of them held here on average
    assert 0 < int(metrics["moe_pairs"]) < 2 * 32 * K
    dense = dataclasses.replace(cfg, moe=None)
    assert "moe_pairs" not in loss_fn(init_params(jax.random.PRNGKey(4), dense), {"tokens": tokens, "labels": tokens},
                                      dense)[1]


@pytest.mark.parametrize("sizes", [(100, 0, 60, 96), (0, 0, 0, 384)], ids=["ragged", "one-group"])
def test_grouped_matmul_kernel_matches_ragged_dot(sizes):
    m, k, n = 512, 256, 384
    sizes = jnp.asarray(sizes, jnp.int32)
    rows = int(sizes.sum())
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    x = jax.random.normal(ks[0], (m, k)).astype(jnp.bfloat16)
    w = (jax.random.normal(ks[1], (4, k, n)) / 16).astype(jnp.bfloat16)
    dy = jax.random.normal(ks[2], (m, n)).astype(jnp.bfloat16)
    mask = (jnp.arange(m) < rows)[:, None]

    def vjp(fn):
        out, back = jax.vjp(lambda x, w: fn(x, w), x, w)
        return (out,) + back(jnp.where(mask, dy, 0).astype(out.dtype))

    got = jax.jit(lambda: vjp(lambda x, w: grouped_matmul_kernel(x, w, sizes, interpret=True)))()
    want = vjp(lambda x, w: jax.lax.ragged_dot(x, w, sizes))
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    np.testing.assert_allclose(f32(got[0])[:rows], f32(want[0])[:rows], rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(f32(got[1])[:rows], f32(want[1])[:rows], rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(f32(got[2]), f32(want[2]), rtol=2e-2, atol=5e-2)
