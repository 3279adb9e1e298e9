"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts: illegal block
shapes, unaligned dynamic slices, programs that do not fit HBM.  Each test
here lowers and compiles for ``v5e:2x2`` devices described by
``jax.experimental.topologies`` and checks what the chip's compiler made:
the Pallas kernels at real widths (a ``tpu_custom_call`` in the HLO) —
the WAN quantizers also at the widest trailing dimension of any config's
parameter leaf, where a slab must still fit VMEM — the full-width distilgpt2-82m train step on one chip (fits its 16 GB),
Mellum2's one-chip stage step (GQA splash and the grouped-matmul kernel), and a
2-pod ``hier_int8`` step on the four-chip host layout (2, 2, 1).  Each
step program, and the serving prefill, must hold the Pallas flash-attention
kernel under the ``attention`` scope (``models/attention.py``).

Nothing runs, so these say nothing about results or speed.  The topology
is described inside a module fixture, never at import: only one process
may hold the TPU library, and the workers of a parallel test run import
every test file.  The persistent compilation cache is off around these
compiles (an executable for a described chip cannot be read back).
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ALL_ARCHS, get_config
from repro.distributed import init_train_state, make_train_step
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rwkv6_wkv import wkv6_fwd
from repro.kernels.wan_quant import dequantize, quantize
from repro.launch.mesh import make_mesh
from repro.launch.shapes import params_specs
from repro.models import prefill
from repro.optim import AdamWConfig

#: device memory of one TPU v5e chip (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
    saved_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", saved_cache)
        compilation_cache.reset_cache()
        if saved_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _peak_bytes(compiled) -> int:
    mem = compiled.memory_analysis()
    return (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    )


def _attention_kernels(compiled):
    """Op names of the compiled program's Pallas kernels that lie under
    ``attention``, in the ``flash`` scope."""
    names = re.findall(
        r'custom_call_target="tpu_custom_call".*?, metadata=\{op_name="([^"]+)"',
        compiled.as_text(), re.S,
    )
    return [n for n in names if {"attention", "flash"} <= set(n.split("/"))]


def _widest_leaf():
    """The parameter leaf with the widest trailing dimension over every config."""
    shapes = (
        leaf.shape for arch in ALL_ARCHS
        for leaf in jax.tree.leaves(params_specs(get_config(arch)))
    )
    return max(shapes, key=lambda shape: shape[-1] if shape else 0)


def _kernel_cases(spec):
    leaf = (6, 768, 3072)  # distilgpt2's stacked MLP up-projection
    rows = 6 * 768
    wide = _widest_leaf()  # rwkv6-7b's unembedding, (4096, 65536)
    wide_rows, wide_lanes = wide[0], -(-wide[-1] // 256) * 256
    bhsd = (8, 12, 1024, 64)  # distilgpt2 attention at batch 8 x 1024
    btnh = (1, 4096, 64, 64)  # rwkv6-7b: 64 heads of 64
    return {
        "wan_quant": (lambda x: quantize(x), [spec(leaf, jnp.float32)]),
        "wan_dequant": (
            lambda q, s: dequantize(q, s, orig_shape=leaf),
            [spec((rows, 3072), jnp.int8), spec((rows, 12), jnp.float32)],
        ),
        "wan_quant_widest": (lambda x: quantize(x), [spec(wide, jnp.float32)]),
        "wan_dequant_widest": (
            lambda q, s: dequantize(q, s, orig_shape=wide),
            [spec((wide_rows, wide_lanes), jnp.int8),
             spec((wide_rows, wide_lanes // 256), jnp.float32)],
        ),
        "flash_attention_fwd": (
            lambda q, k, v: flash_attention_fwd(q, k, v),
            [spec(bhsd, jnp.bfloat16)] * 3,
        ),
        "wkv6_fwd": (
            lambda r, k, v, w, u, s: wkv6_fwd(r, k, v, w, u, s, chunk=128),
            [spec(btnh, jnp.bfloat16)] * 4
            + [spec((64, 64), jnp.float32), spec((1, 64, 64, 64), jnp.float32)],
        ),
    }


@pytest.mark.parametrize(
    "kernel",
    ["wan_quant", "wan_dequant", "wan_quant_widest", "wan_dequant_widest",
     "flash_attention_fwd", "wkv6_fwd"],
)
def test_kernel_compiles_for_v5e(one_chip, kernel):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _kernel_cases(spec)[kernel]
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()


def _lower_train_step(cfg, mesh, strategy, *, seq_len=1024, global_batch=8):
    p_shapes = params_specs(cfg)
    b_shapes = {
        "tokens": jax.ShapeDtypeStruct((global_batch, seq_len), jnp.int32),
        "labels": jax.ShapeDtypeStruct((global_batch, seq_len), jnp.int32),
    }
    s_shapes = jax.eval_shape(
        lambda p: init_train_state(p, AdamWConfig(), strategy=strategy), p_shapes
    )
    with mesh:
        step, _ = make_train_step(
            cfg, mesh, strategy=strategy, params_shapes=p_shapes,
            batch_shapes=b_shapes, donate=False,
        )
        return step.lower(p_shapes, s_shapes, b_shapes)


def test_full_width_train_step_fits_one_chip(topo):
    """distilgpt2-82m, global batch 8 x 1024, ``hier``, on one v5e chip."""
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    compiled = _lower_train_step(get_config("distilgpt2-82m"), mesh, "hier").compile()
    assert 0 < _peak_bytes(compiled) < V5E_HBM_BYTES
    assert _attention_kernels(compiled)


@pytest.mark.parametrize("program", ["olmo-1b-l4.train.b4x2048", "distilgpt2-82m.prefill.b128x512"])
def test_attention_kernel_in_program(topo, one_chip, program):
    """The benchmark's other two programs on one v5e chip: OLMo-1B's first
    4 layers (remat full) trained at 4 x 2048, and distilgpt2-82m's prefill
    of 128 prompts of 512 tokens into a 576-position cache."""
    if program.startswith("olmo"):
        mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
        cfg = dataclasses.replace(get_config("olmo-1b"), num_layers=4)
        compiled = _lower_train_step(cfg, mesh, "hier", seq_len=2048, global_batch=4).compile()
    else:
        cfg = get_config("distilgpt2-82m")
        params = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
            params_specs(cfg),
        )
        batch = {"tokens": jax.ShapeDtypeStruct((128, 512), jnp.int32, sharding=one_chip)}
        compiled = _compile(lambda p, b: prefill(p, b, cfg, max_len=576), params, batch)
    assert 0 < _peak_bytes(compiled) < V5E_HBM_BYTES
    assert _attention_kernels(compiled)


def test_mellum2_stage_train_step_fits_one_chip(topo):
    """mellum2-12b-a2.5b-l4e8 (4 layers of 2304, GQA 32/4, 8 of 64 experts
    held, 12,288 vocabulary rows) trained at 2 x 8192 on one v5e chip: the
    GQA attention of its sliding and full layers in the splash kernel, and
    the held experts' matmuls in the grouped-matmul kernel."""
    from repro.configs.mellum2_12b_a2_5b import stage_config

    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    compiled = _lower_train_step(stage_config(), mesh, "hier", seq_len=8192, global_batch=2).compile()
    assert 0 < _peak_bytes(compiled) < V5E_HBM_BYTES
    assert len(_attention_kernels(compiled)) >= 3 * 4  # forward, remat's forward, backward; 4 layers
    names = re.findall(
        r'custom_call_target="tpu_custom_call".*?, metadata=\{op_name="([^"]+)"', compiled.as_text(), re.S,
    )
    experts = [n for n in names if "moe_experts" in n.split("/")]
    assert len(experts) >= 3 * 4 * 4  # 3 matmuls a layer: forward, remat's forward, 2 in the backward


def test_two_pod_hier_int8_step_compiles(topo):
    """The cross-pod int8 sync on the four-chip host layout (pod=2, data=2, model=1)."""
    mesh = make_mesh((2, 2, 1), ("pod", "data", "model"), devices=topo.devices[:4])
    compiled = _lower_train_step(get_config("distilgpt2-82m"), mesh, "hier_int8").compile()
    hlo = compiled.as_text()
    assert "all-gather" in hlo  # the int8 payload and its scales cross the pods
    assert 0 < _peak_bytes(compiled) < V5E_HBM_BYTES
    assert _attention_kernels(compiled)  # per device, under shard_map over "data"
