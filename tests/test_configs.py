"""Config validation: exact param counts (eval_shape) vs published sizes."""

import jax
import pytest

from repro.configs import ALL_ARCHS, ASSIGNED_ARCHS, EXPECTED_PARAMS, get_config, get_smoke_config
from repro.launch.shapes import SHAPES, params_specs, shape_supported

LONG_CTX_ARCHS = {"mixtral-8x22b", "rwkv6-7b", "recurrentgemma-9b"}


def test_registry_complete():
    assert len(ASSIGNED_ARCHS) == 10
    assert "distilgpt2-82m" in ALL_ARCHS  # the paper's own model


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_param_count_matches_published(arch):
    cfg = get_config(arch)
    specs = params_specs(cfg)
    n = sum(s.size for s in jax.tree.leaves(specs))
    expected = EXPECTED_PARAMS[arch]
    assert abs(n - expected) / expected < 0.12, (
        f"{arch}: {n / 1e9:.2f}B params vs published {expected / 1e9:.2f}B"
    )


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_exact_dims(arch):
    """The registry must carry the assignment's exact dims."""
    dims = {
        "phi-3-vision-4.2b": (32, 3072, 32, 32, 8192, 32064),
        "starcoder2-7b": (32, 4608, 36, 4, 18432, 49152),
        "chatglm3-6b": (28, 4096, 32, 2, 13696, 65024),
        "olmo-1b": (16, 2048, 16, 16, 8192, 50304),
        "yi-34b": (60, 7168, 56, 8, 20480, 64000),
        "arctic-480b": (35, 7168, 56, 8, 4864, 32000),
        "mixtral-8x22b": (56, 6144, 48, 8, 16384, 32768),
        "rwkv6-7b": (32, 4096, 64, 64, 14336, 65536),
        "musicgen-large": (48, 2048, 32, 32, 8192, 2048),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "distilgpt2-82m": (6, 768, 12, 12, 3072, 50257),
        "mellum2-12b-a2.5b": (28, 2304, 32, 4, 896, 98304),
    }[arch]
    cfg = get_config(arch)
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.d_ff, cfg.vocab_size)
    assert got == dims


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_config_same_family(arch):
    full, smoke = get_config(arch), get_smoke_config(arch)
    assert smoke.family == full.family
    assert smoke.pattern == full.pattern
    assert smoke.norm == full.norm
    assert smoke.activation == full.activation
    assert (smoke.moe is None) == (full.moe is None)
    assert smoke.frontend == full.frontend
    assert smoke.param_count() < 10e6  # genuinely reduced


def test_moe_flags():
    arctic = get_config("arctic-480b")
    assert arctic.moe.num_experts == 128 and arctic.moe.num_experts_per_tok == 2
    assert arctic.moe.parallel_dense  # dense residual
    mixtral = get_config("mixtral-8x22b")
    assert mixtral.moe.num_experts == 8 and mixtral.window is not None


def test_long_500k_eligibility():
    """Sub-quadratic archs run long_500k; pure full-attention archs skip."""
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        ok, why = shape_supported(cfg, "long_500k")
        assert ok == (arch in LONG_CTX_ARCHS), (arch, why)


def test_every_arch_runs_other_shapes():
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        for shape in ("train_4k", "prefill_32k", "decode_32k"):
            ok, _ = shape_supported(cfg, shape)
            assert ok


def test_shape_table():
    assert SHAPES["train_4k"].seq_len == 4096 and SHAPES["train_4k"].global_batch == 256
    assert SHAPES["prefill_32k"].seq_len == 32768 and SHAPES["prefill_32k"].global_batch == 32
    assert SHAPES["decode_32k"].seq_len == 32768 and SHAPES["decode_32k"].global_batch == 128
    assert SHAPES["long_500k"].seq_len == 524288 and SHAPES["long_500k"].global_batch == 1
