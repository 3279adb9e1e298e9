"""The Pallas flash kernel on the attention path (``models/attention.py``).

Two things are checked here, on the CPU:

* the kernel (JAX's splash attention, in Pallas interpret mode) computes
  what the jnp dense form computes, forward and backward, within bf16
  rounding, also with GQA, a sliding window and a logit softcap, and also
  split over a mesh by ``map_batch_shards``;
* ``attention_forward`` takes the kernel exactly when its conditions hold.
  The platform is steered by lowering for ``tpu`` (no chip is needed to
  lower); a CPU lowering, decode, a length that is not a multiple of 128
  and an explicit ``attn_impl`` keep the jnp forms.

``tests/test_tpu_compile.py`` compiles the same path for a described v5e.
"""

import dataclasses
import functools
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import decode_step, init_decode_cache, init_params, loss_fn, prefill
from repro.models.attention import _sdpa_dense, _sdpa_flash

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _inputs(seed, b, s, h, kvh, hd):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, s, h, hd)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, kvh, hd)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, kvh, hd)).astype(jnp.bfloat16)
    do = jax.random.normal(ks[3], (b, s, h, hd)).astype(jnp.bfloat16)
    return q, k, v, do


def _out_and_grads(fn, q, k, v, do):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out,) + vjp(do.astype(out.dtype))


def _rel(x, ref):
    x = np.asarray(x, np.float32)
    return float(np.linalg.norm(x - ref) / np.linalg.norm(ref))


def _check_against_dense(b, s, h, kvh, hd, window=None, softcap=None):
    q, k, v, do = _inputs(s + hd + h, b, s, h, kvh, hd)
    pos = jnp.arange(s)

    def dense(q, k, v):
        return _sdpa_dense(
            q, k, v, q_positions=pos, k_positions=pos, window=window, logit_softcap=softcap
        )

    def kernel(q, k, v):
        return _sdpa_flash(q, k, v, window=window, logit_softcap=softcap, interpret=True)

    got = jax.jit(lambda *a: _out_and_grads(kernel, *a))(q, k, v, do)
    # the float32 truth on the same (bf16-valued) operands, and the jnp form
    # in bf16, whose rounding sets the tolerance
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    exact = _out_and_grads(dense, f32(q), f32(k), f32(v), do)
    bf16 = _out_and_grads(dense, q, k, v, do)
    for name, g, e, r in zip(("out", "dq", "dk", "dv"), got, exact, bf16):
        e = np.asarray(e, np.float32)
        assert g.shape == e.shape, name
        assert _rel(g, e) < 5e-3, (name, _rel(g, e), _rel(r, e))
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(r, np.float32), rtol=2e-2, atol=2e-2,
            err_msg=name,
        )


@pytest.mark.parametrize("s", [256, 512])
@pytest.mark.parametrize("hd", [64, 128])
def test_kernel_matches_dense(s, hd):
    _check_against_dense(2, s, 2, 2, hd)


@pytest.mark.parametrize(
    "h,kvh,window,softcap",
    [(4, 2, None, None), (4, 1, None, None), (2, 2, 64, None), (2, 2, None, 30.0)],
    ids=["gqa", "mqa", "window", "softcap"],
)
def test_kernel_masks_match_dense(h, kvh, window, softcap):
    _check_against_dense(2, 256, h, kvh, 64, window=window, softcap=softcap)


def test_kernel_key_gradient_sums_to_zero_as_dense():
    """Each row of the score gradient sums to zero (softmax is shift
    invariant), so the keys' gradient summed over positions is rounding
    alone.  The kernel's backward takes those row sums from its output;
    rounded to bf16, that output made this sum's error 1.7 times the dense
    form's, which a key bias under rotary positions collects."""
    s = 512
    q, k, v, do = _inputs(0, 1, s, 2, 2, 64)
    pos = jnp.arange(s)

    def dense(q, k, v):
        return _sdpa_dense(q, k, v, q_positions=pos, k_positions=pos, window=None, logit_softcap=None)

    def kernel(q, k, v):
        return _sdpa_flash(q, k, v, window=None, logit_softcap=None, interpret=True)

    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    exact = _out_and_grads(dense, f32(q), f32(k), f32(v), do)[2]

    def summed_error(dk):
        return float(jnp.linalg.norm(f32(dk).sum(1) - exact.sum(1)) / jnp.linalg.norm(exact))

    got = jax.jit(lambda *a: _out_and_grads(kernel, *a))(q, k, v, do)[2]
    bf16 = _out_and_grads(dense, q, k, v, do)[2]
    assert summed_error(got) < 1.3 * summed_error(bf16)


def test_kernel_split_over_mesh_matches_dense():
    """Under ``activation_sharding`` on four devices, with the batch over
    ``data`` and the heads over ``model``, and with a manual ``pod`` axis
    around it, the kernel gives the unsplit answer."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.distributed.act_sharding import activation_sharding
    from repro.launch.mesh import make_mesh
    from repro.models.attention import _sdpa_dense, _sdpa_flash

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (4, 256, 4, 64)).astype(jnp.bfloat16)
    k, v = (jax.random.normal(kk, (4, 256, 2, 64)).astype(jnp.bfloat16) for kk in ks[1:])
    pos = jnp.arange(256)
    ref = _sdpa_dense(q, k, v, q_positions=pos, k_positions=pos, window=None, logit_softcap=None)

    def attend(q, k, v):
        return _sdpa_flash(q, k, v, window=None, logit_softcap=None, interpret=True)

    mesh = make_mesh((2, 2), ("data", "model"))
    def flat(q, k, v):
        with activation_sharding("data", "model", mesh):
            return attend(q, k, v)
    hlo = jax.jit(flat).lower(q, k, v).as_text()
    got = jax.jit(flat)(q, k, v)
    print("flat", float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref))))
    print("flat-split", "tensor<2x256x2x64xbf16>" in hlo)

    mesh = make_mesh((2, 2, 1), ("pod", "data", "model"))
    def inner(q, k, v):
        with activation_sharding("data", "model", mesh):
            return attend(q, k, v)
    pods = jax.shard_map(inner, mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
                         axis_names={"pod"}, check_vma=False)
    got = jax.jit(pods)(q, k, v)
    print("pods", float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref))))
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    lines = dict(line.split(" ", 1) for line in out.stdout.strip().splitlines())
    assert float(lines["flat"]) < 3e-2
    assert float(lines["pods"]) < 3e-2
    assert lines["flat-split"] == "True"  # each device holds 2 rows x 2 heads


# -- which path attention_forward takes -------------------------------------------


def _cfg(**kw):
    base = dict(num_layers=1, vocab_size=256)
    base.update(kw)
    return dataclasses.replace(get_config("distilgpt2-82m"), **base)


def _tokens(b, s):
    return jax.ShapeDtypeStruct((b, s), jnp.int32)


def _has_kernel(fn, *args, platform="tpu") -> bool:
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=(platform,))
    return "tpu_custom_call" in lowered.as_text()


def _train(cfg, s, platform="tpu"):
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    batch = {"tokens": _tokens(2, s), "labels": _tokens(2, s)}
    grad = lambda p, b: jax.grad(lambda p: loss_fn(p, b, cfg)[0])(p)
    return _has_kernel(grad, params, batch, platform=platform)


def _prefill(cfg, s):
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    fn = lambda p, b: prefill(p, b, cfg, max_len=s + 64)
    return _has_kernel(fn, params, {"tokens": _tokens(2, s)})


@pytest.mark.parametrize("s", [256, 1024])
def test_train_takes_kernel_on_tpu(s):
    assert _train(_cfg(), s)


def test_prefill_takes_kernel_on_tpu():
    assert _prefill(_cfg(), 512)


def test_cpu_keeps_jnp():
    assert not _train(_cfg(), 256, platform="cpu")


def test_length_off_128_keeps_jnp():
    assert not _train(_cfg(), 192)
    assert not _prefill(_cfg(), 320)


@pytest.mark.parametrize("impl", ["naive", "chunked", "chunked_kv"])
def test_explicit_impl_keeps_jnp(impl):
    assert not _train(_cfg(attn_impl=impl, attn_block=128), 256)


@pytest.mark.parametrize(
    "kw", [dict(window=64), dict(attn_logit_softcap=30.0)], ids=["window", "softcap"]
)
def test_window_and_softcap_take_kernel(kw):
    """The kernel computes both as the jnp forms do (test_kernel_masks_match_dense)."""
    assert _train(_cfg(**kw), 256)


def test_decode_keeps_jnp():
    cfg = _cfg()
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: init_decode_cache(cfg, 2, 512))
    fn = lambda p, t, c: decode_step(p, t, c, cfg, jnp.int32(300))
    assert not _has_kernel(fn, params, _tokens(2, 1).update(shape=(2,)), cache)
