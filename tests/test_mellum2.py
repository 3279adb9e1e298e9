"""Mellum2-12B-A2.5B (``repro.configs.mellum2_12b_a2_5b``) on the normal path.

* YaRN's frequencies and attention factor against the published formula
  (arXiv 2309.00071, as Hugging Face's ``yarn`` rope type computes them);
* grouped-query attention (8 query heads a key/value head, as 32/4; here
  16/2) in the splash kernel, Pallas interpret mode, against the jnp form
  under the causal and the sliding-window mask, forward and gradients;
* at a tiny size, the configuration's loss and gradients against its plain
  reference (``benchmarks/chip/references/mellum2-12b-a2.5b-l4e8.py``), and
  prefill then ``decode_step`` against the forward pass's logits;
* the one-chip stage is the published configuration cut, the benchmark's
  configuration file is that stage, YaRN is named for the full layers
  only, and every parameter count agrees with the parameter tree.
"""

import dataclasses
import functools
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.mellum2_12b_a2_5b import stage_config
from repro.launch.shapes import params_specs
from repro.models import decode_step, forward, init_params, loss_fn, prefill
from repro.models.attention import _sdpa_dense, _sdpa_flash
from repro.models.config import ATTN, ModelConfig, YarnConfig
from repro.models.layers import apply_rope, yarn_frequencies

ROOT = Path(__file__).resolve().parents[1]
CONFIG_FILE = ROOT / "benchmarks" / "chip" / "configs" / "mellum2-12b-a2.5b-l4e8.json"


def _published_yarn(dim, base, factor, original, beta_fast, beta_slow):
    """Hugging Face's ``_compute_yarn_parameters``, in float64."""
    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (np.arange(0, dim, 2) / dim)
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)
    extrapolation_factor = 1 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = interpolation * (1 - extrapolation_factor) + extrapolation * extrapolation_factor
    return inv, 0.1 * math.log(factor) + 1.0


@pytest.mark.parametrize("theta,factor,original", [(500_000.0, 16.0, 8192), (10_000.0, 4.0, 2048)])
def test_yarn_frequencies_are_the_published_formula(theta, factor, original):
    yarn = YarnConfig(kinds=(ATTN,), factor=factor, original_max_position_embeddings=original)
    inv, rot_dim, scale = yarn_frequencies(128, 1.0, theta, yarn)
    want, want_scale = _published_yarn(128, theta, factor, original, 32, 1)
    assert rot_dim == 128
    np.testing.assert_allclose(np.asarray(inv), want, rtol=2e-6)
    assert scale == pytest.approx(want_scale)
    # Mellum2's config states the factor the formula gives
    assert get_config("mellum2-12b-a2.5b").yarn.attention_factor == pytest.approx(
        _published_yarn(128, 5e5, 16.0, 8192, 32, 1)[1], rel=1e-15)
    # high frequencies kept, low ones interpolated by the factor
    assert float(inv[0]) == pytest.approx(1.0)
    assert float(inv[-1]) == pytest.approx(want[-1]) and want[-1] == pytest.approx(
        1.0 / theta ** (126 / 128) / factor)


def test_yarn_rotary_scales_both_halves_by_the_attention_factor():
    yarn = YarnConfig(kinds=(ATTN,), factor=16.0, original_max_position_embeddings=8192,
                      attention_factor=1.25)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 2, 16))
    pos = jnp.zeros(4, jnp.int32)  # no rotation at position 0
    np.testing.assert_allclose(apply_rope(x, pos, yarn=yarn), 1.25 * x, rtol=1e-6)
    np.testing.assert_array_equal(apply_rope(x, pos), x)


def _gqa_case(window):
    b, s, h, kvh, hd = 1, 512, 16, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(window or 1), 4)
    q = jax.random.normal(ks[0], (b, s, h, hd)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, kvh, hd)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, kvh, hd)).astype(jnp.bfloat16)
    do = jax.random.normal(ks[3], (b, s, h, hd)).astype(jnp.bfloat16)
    return q, k, v, do


@pytest.mark.parametrize("window", [None, 128], ids=["causal", "sliding"])
def test_gqa_splash_matches_the_jnp_form(window):
    q, k, v, do = _gqa_case(window)
    pos = jnp.arange(q.shape[1])
    dense = functools.partial(_sdpa_dense, q_positions=pos, k_positions=pos, window=window, logit_softcap=None)
    kernel = functools.partial(_sdpa_flash, window=window, logit_softcap=None, interpret=True)

    def out_and_grads(fn, *args):
        out, vjp = jax.vjp(fn, *args)
        return (out,) + vjp(do.astype(out.dtype))

    got = jax.jit(lambda *a: out_and_grads(kernel, *a))(q, k, v)
    exact = out_and_grads(dense, *(a.astype(jnp.float32) for a in (q, k, v)))
    for name, g, e in zip(("out", "dq", "dk", "dv"), got, exact):
        g, e = np.asarray(g, np.float32), np.asarray(e, np.float32)
        assert g.shape == e.shape, name
        assert np.linalg.norm(g - e) / np.linalg.norm(e) < 5e-3, name


def _tiny():
    """The one-chip stage at a tiny size, as a mapping: every kind of layer,
    GQA, held experts from the middle of the router's range, a window and
    YaRN that act within 32 positions."""
    m = dataclasses.asdict(stage_config())
    m.update(d_model=64, num_heads=8, num_kv_heads=2, head_dim=16, d_ff=32, vocab_size=128,
             local_window=8, dtype="float32")
    m["moe"].update(num_experts=16, num_experts_per_tok=4, held=4, first_held=4)
    m["yarn"].update(original_max_position_embeddings=16, factor=4.0, attention_factor=None)
    return m


def test_loss_and_gradients_match_the_plain_reference():
    sys.path.insert(0, str(ROOT))
    from benchmarks.chip import inputs, reference

    m = _tiny()
    cfg = ModelConfig(**m)  # the mappings of ``moe`` and ``yarn`` as a file gives them
    params = jax.tree.map(lambda a: a.astype(jnp.float32), init_params(jax.random.PRNGKey(7), cfg))
    batch = inputs.train_pool(7, cfg.vocab_size, 1, 2, 32)[0]
    ref = reference.lookup("mellum2-12b-a2.5b-l4e8")
    assert ref.source.endswith("references/mellum2-12b-a2.5b-l4e8.py")
    want_loss, want_grad = ref.loss_and_grad(params, batch["tokens"], batch["labels"], m, "f32", 2)
    with jax.default_matmul_precision("highest"):
        (loss, metrics), grad = jax.jit(jax.value_and_grad(lambda p: loss_fn(p, batch, cfg), has_aux=True))(params)
    assert float(loss) == pytest.approx(want_loss, rel=1e-5)
    assert float(metrics["aux"]) > 0
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grad), jax.tree.leaves(want_grad)):
        np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-6, err_msg=jax.tree_util.keystr(path))


def test_prefill_then_decode_matches_the_forward_pass():
    cfg = ModelConfig(**_tiny())
    params = init_params(jax.random.PRNGKey(8), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 24), 0, cfg.vocab_size)
    full, _ = forward(params, {"tokens": tokens}, cfg)
    n0 = 12  # past the window of 8: the local layers' caches roll
    last, cache = prefill(params, {"tokens": tokens[:, :n0]}, cfg, max_len=24)
    np.testing.assert_allclose(last, full[:, n0 - 1], rtol=1e-4, atol=1e-4)
    for t in range(n0, 24):
        logits, cache = decode_step(params, tokens[:, t], cache, cfg, jnp.int32(t))
        np.testing.assert_allclose(logits, full[:, t], rtol=1e-4, atol=1e-4, err_msg=f"t={t}")


def test_stage_is_the_published_configuration_cut():
    published = get_config("mellum2-12b-a2.5b")
    assert stage_config() == dataclasses.replace(
        published, name="mellum2-12b-a2.5b-l4e8", num_layers=4, vocab_size=12288,
        moe=dataclasses.replace(published.moe, held=8, first_held=0))
    assert published.moe.num_experts == 64 and published.moe.num_held == 64
    # the source's rope_parameters: YaRN for full_attention, default for sliding_attention
    assert published.yarn.kinds == (ATTN,)
    # a mapping (a configuration file's) builds the same configuration
    assert ModelConfig(**dataclasses.asdict(stage_config())) == stage_config()


def test_configuration_file_is_the_stage():
    sys.path.insert(0, str(ROOT))
    from benchmarks.chip.drive_train import model_config

    spec = json.loads(CONFIG_FILE.read_text())
    assert model_config(spec) == stage_config()
    # the file's top level keeps the source config's keys; the cut ones are listed
    assert (spec["num_hidden_layers"], spec["vocab_size"], spec["num_experts_held"]) == (4, 12288, 8)
    assert spec["num_experts"] == stage_config().moe.num_experts == 64
    assert set(spec["reduced"]) == {"num_hidden_layers", "layer_types", "mlp_layer_types", "vocab_size",
                                    "num_experts_held"}


def test_yarn_reaches_only_the_kinds_it_names():
    from repro.models.transformer import _layer_attention

    cfg = stage_config()
    assert [_layer_attention(k, cfg)["yarn"] for k in cfg.pattern] == [None, None, None, cfg.yarn]
    both = dataclasses.replace(cfg, yarn=dataclasses.replace(cfg.yarn, kinds=("local_attn", "attn")))
    assert all(_layer_attention(k, both)["yarn"] is both.yarn for k in both.pattern)
    with pytest.raises(ValueError):
        YarnConfig(kinds=("rwkv",), factor=4.0, original_max_position_embeddings=16)


@pytest.mark.parametrize("which", ["published", "stage"])
def test_parameter_counts_agree_with_the_tree(which):
    cfg = get_config("mellum2-12b-a2.5b") if which == "published" else stage_config()
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(params_specs(cfg)))
    assert cfg.param_count() == n
    assert n == {"published": 12_149_915_904, "stage": 340_349_184}[which]
    # a token reaches k * held / num_experts of the held experts: 8 of 64, or 1 of 8 held
    expert = 3 * 2304 * 896
    inactive = 28 * 56 * expert if which == "published" else 4 * 7 * expert
    assert cfg.active_param_count() == n - inactive


def test_rmsnorm_gain_holds_the_gain_itself():
    """``rmsnorm_gain`` (Mellum2's checkpoint form) multiplies by its weight;
    ``rmsnorm`` by one plus its weight; both start as the identity gain."""
    from repro.models.layers import apply_norm, init_norm

    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 64))
    gain = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(4), (64,))
    cfgs = {n: dataclasses.replace(ModelConfig(**_tiny()), norm=n) for n in ("rmsnorm", "rmsnorm_gain")}
    np.testing.assert_allclose(apply_norm({"scale": gain}, x, cfgs["rmsnorm_gain"]),
                               apply_norm({"scale": gain - 1.0}, x, cfgs["rmsnorm"]), rtol=1e-6, atol=1e-6)
    for cfg in cfgs.values():
        unit = apply_norm(init_norm(None, cfg), x, cfg)
        np.testing.assert_allclose(unit * jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6), x, rtol=1e-5, atol=1e-5)
