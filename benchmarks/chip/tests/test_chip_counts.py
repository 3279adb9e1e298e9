"""FLOP, byte and peak counts, and the configuration files against the program's."""

import dataclasses
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import counts  # noqa: E402

CONFIGS = ROOT / "benchmarks" / "chip" / "configs"


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


@pytest.mark.parametrize("name", ["distilgpt2-82m", "olmo-1b-l4"])
def test_param_count_matches_the_program(name):
    import jax
    import numpy as np

    from benchmarks.chip.drive_train import model_config
    from repro.launch.shapes import params_specs

    cfg = model_config({"model": model(name)})
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(params_specs(cfg)))
    assert counts.param_count(model(name)) == n


def test_configuration_files_are_the_repo_configs():
    from benchmarks.chip.drive_train import model_config
    from repro.configs import get_config

    assert model_config({"model": model("distilgpt2-82m")}) == get_config("distilgpt2-82m")
    olmo = dataclasses.replace(get_config("olmo-1b"), name="olmo-1b-l4", num_layers=4)
    assert model_config({"model": model("olmo-1b-l4")}) == olmo


def test_decode_bytes_of_the_serving_cell():
    m = model("distilgpt2-82m")
    n = 81_126_144  # every parameter of distilgpt2-82m once
    ctx = counts.mean_decode_context(512, 64)
    assert ctx == 512 + 32
    kv_per_position = 2 * 6 * 128 * 12 * 64 * 2  # K and V, layers, batch, heads, head_dim, bf16
    assert counts.decode_bytes(m, 128, ctx) == pytest.approx(2 * n + ctx * kv_per_position)
    assert counts.decode_bytes(m, 128, ctx) == pytest.approx(162.25e6 + 1283.46e6, rel=1e-3)


def test_training_and_prefill_flops():
    m = model("distilgpt2-82m")
    assert counts.train_flops_per_token(m, 1024) == 6 * 81_126_144 + 12 * 6 * 768 * 1024
    body = 81_126_144 - 50257 * 768
    assert counts.prefill_flops(m, 8, 512) == 8 * (512 * (2 * body + 4 * 6 * 768 * 512) + 2 * 768 * 50257)


def test_unknown_device_kind_raises():
    assert counts.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        counts.peaks("cpu")
