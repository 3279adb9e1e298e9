"""Distribution layer tests.

In-process tests cover sharding rules and compression (1 device is fine).
Multi-device behaviour (manual-pod shard_map, strategy equivalence) runs in
a subprocess with ``--xla_force_host_platform_device_count=4`` because the
main pytest process must keep seeing exactly one device.  The pod meshes
are the two layouts a four-chip TPU v5e host can hold, (pod, data, model) =
(2, 2, 1) and (2, 1, 2).
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.distributed.compression import (
    BLOCK,
    compressed_bytes,
    init_error_feedback,
    int8_compress,
    int8_decompress,
    topk_densify,
    topk_sparsify,
)
from repro.distributed.sync import wan_bytes_per_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


#: (pod, data, model) layouts of a four-chip host.  A 2x2x2 mesh is not among
#: them: on 8 fake CPU devices it aborts in XLA's CPU SPMD partitioner
#: (``spmd_partitioner_util.cc`` replica-group CHECK) for every strategy,
#: while the same step compiles for a described ``v5e:2x4`` — a CPU-backend
#: bug, not a fault of the step's sharding constraints.
POD_MESHES = [(2, 2, 1), (2, 1, 2)]


def run_subprocess(code: str, timeout: int = 900, *, prelude: str = "") -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", prelude + textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"subprocess failed:\n{out.stdout}\n{out.stderr[-3000:]}"
    return out.stdout


# -- compression (in-process) ----------------------------------------------------


class TestInt8Compression:
    @given(
        st.sampled_from([(64,), (3, 100), (2, 256), (5, 7, 300)]),
        st.integers(min_value=0, max_value=2**31 - 1),
    )
    @settings(max_examples=20, deadline=None)
    def test_roundtrip_error_bound(self, shape, seed):
        """|x - deq(q(x))| <= absmax/254 per block (half a quant step)."""
        x = jax.random.normal(jax.random.PRNGKey(seed), shape) * 3.0
        c = int8_compress(x)
        back = int8_decompress(c)
        assert back.shape == x.shape
        err = jnp.abs(back - x)
        bound = jnp.max(jnp.abs(x)) / 254.0 + 1e-7
        assert float(err.max()) <= float(bound) * 1.01

    def test_compression_ratio(self):
        x = jnp.ones((1024, 1024), jnp.float32)
        c = int8_compress(x)
        ratio = (x.size * 4) / compressed_bytes(c)
        assert ratio > 3.8  # ~4x minus scale overhead

    def test_zeros_safe(self):
        c = int8_compress(jnp.zeros((512,)))
        np.testing.assert_array_equal(np.asarray(int8_decompress(c)), 0.0)

    def test_preserves_leading_sharding_shape(self):
        """Blocks run along the last dim only — leading dims untouched."""
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 512))
        c = int8_compress(x)
        assert c.values.shape == (8, 512)
        assert c.scales.shape == (8, 2)

    def test_error_feedback_converges(self):
        """With EF, the *accumulated* transmitted signal tracks the true
        gradient sum even though each step quantizes coarsely."""
        key = jax.random.PRNGKey(1)
        g_true = jax.random.normal(key, (4, BLOCK)) * 1e-3
        ef = init_error_feedback({"g": g_true})["g"]
        sent_total = jnp.zeros_like(g_true)
        for _ in range(50):
            boosted = g_true + ef
            c = int8_compress(boosted)
            sent = int8_decompress(c)
            ef = boosted - sent
            sent_total = sent_total + sent
        np.testing.assert_allclose(
            np.asarray(sent_total), np.asarray(g_true * 50), rtol=0.02, atol=1e-5
        )


class TestTopK:
    def test_roundtrip(self):
        x = jnp.arange(100.0).reshape(10, 10)
        vals, idx, shape = topk_sparsify(x, k_fraction=0.1)
        dense = topk_densify(vals, idx, shape)
        assert float(dense.sum()) == float(sum(range(90, 100)))
        assert dense.shape == x.shape


class TestWanBytes:
    def test_strategy_ordering(self):
        p = 328_000_000  # distilgpt2 fp32 bytes
        ar = wan_bytes_per_step(p, "allreduce")
        ps = wan_bytes_per_step(p, "ps")
        i8 = wan_bytes_per_step(p, "hier_int8")
        ls = wan_bytes_per_step(p, "local_sgd")
        assert ps > ar > i8 > ls == 0.0


# -- sharding rules (in-process, no devices needed) --------------------------------


class TestShardingRules:
    def _mesh(self):
        # 1-device "mesh" is enough to evaluate pure spec logic
        from repro.launch.mesh import make_mesh

        return make_mesh((1, 1, 1), ("pod", "data", "model"))

    def test_divisibility_fallback(self):
        from jax.sharding import PartitionSpec as P
        from repro.distributed.sharding import params_pspecs
        from repro.launch.mesh import make_mesh
        # on a 1x1x1 mesh everything divides; use spec structure checks
        mesh = self._mesh()
        shapes = {"groups": {"slot0": {"attn": {
            "wq": jax.ShapeDtypeStruct((2, 64, 64), jnp.float32),
            "wo": jax.ShapeDtypeStruct((2, 64, 64), jnp.float32),
        }}}}
        specs = params_pspecs(shapes, mesh)
        wq = specs["groups"]["slot0"]["attn"]["wq"]
        assert wq == P(None, "data", "model")
        wo = specs["groups"]["slot0"]["attn"]["wo"]
        assert wo == P(None, "model", "data")

    def test_embed_never_data_sharded(self):
        from jax.sharding import PartitionSpec as P
        from repro.distributed.sharding import params_pspecs

        mesh = self._mesh()
        specs = params_pspecs({"embed": jax.ShapeDtypeStruct((256, 64), jnp.float32)}, mesh)
        assert "data" not in jax.tree.leaves(specs["embed"]) if specs["embed"] else True
        assert specs["embed"] == P("model", None)

    def test_moe_expert_parallel(self):
        from jax.sharding import PartitionSpec as P
        from repro.distributed.sharding import params_pspecs

        mesh = self._mesh()
        shapes = {"groups": {"slot0": {"ffn": {
            "w_up": jax.ShapeDtypeStruct((2, 8, 64, 128), jnp.float32),
            "w_down": jax.ShapeDtypeStruct((2, 8, 128, 64), jnp.float32),
            "router": jax.ShapeDtypeStruct((2, 64, 8), jnp.float32),
        }}}}
        specs = params_pspecs(shapes, mesh)
        assert specs["groups"]["slot0"]["ffn"]["w_up"] == P(None, "model", None, "data")
        assert specs["groups"]["slot0"]["ffn"]["w_down"] == P(None, "model", "data", None)

    def test_batch_pspec_divisibility(self):
        from jax.sharding import PartitionSpec as P
        from repro.distributed.sharding import batch_pspecs
        from repro.launch.mesh import make_mesh

        mesh = self._mesh()
        shapes = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
        specs = batch_pspecs(shapes, mesh)
        assert specs["tokens"][0] == ("pod", "data")
        odd = {"tokens": jax.ShapeDtypeStruct((1, 16), jnp.int32)}
        # batch=1 divides a 1x1x1 mesh; structure is what matters here
        assert batch_pspecs(odd, mesh)["tokens"][0] == ("pod", "data")


# -- multi-device behaviour (subprocess) -------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("mesh_shape", POD_MESHES, ids=lambda s: "x".join(map(str, s)))
def test_strategies_on_fake_pods(mesh_shape):
    """All five sync strategies compile and train on a 2-pod fake mesh, and
    the per-step loss trajectory of allreduce == hier == hier_int8 == ps.

    Runs on the four-chip host layouts (see ``POD_MESHES``): 2x2x2 trips
    XLA's CPU partitioner only."""
    out = run_subprocess(
        """
        import jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_mesh
        from repro.launch.shapes import params_specs
        from repro.models import init_params
        from repro.distributed import make_train_step, init_train_state
        from repro.optim import AdamWConfig

        mesh = make_mesh(MESH_SHAPE, ("pod", "data", "model"))
        cfg = get_smoke_config("distilgpt2-82m")
        key = jax.random.PRNGKey(0)
        B, S = 8, 16
        p_shapes = params_specs(cfg)
        b_shapes = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
                    "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        results = {}
        for strategy in ("allreduce", "hier", "hier_int8", "ps", "local_sgd"):
            with mesh:
                step, _ = make_train_step(cfg, mesh, opt_cfg=AdamWConfig(warmup_steps=1),
                                          strategy=strategy, params_shapes=p_shapes,
                                          batch_shapes=b_shapes, donate=False)
                params = init_params(key, cfg)
                state = init_train_state(params, AdamWConfig(), strategy=strategy)
                toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
                batch = {"tokens": toks, "labels": toks}
                losses = []
                for _ in range(2):
                    params, state, m = step(params, state, batch)
                    losses.append(float(m["loss"]))
                results[strategy] = losses
                assert losses[1] < losses[0], (strategy, losses)
        for s in ("hier", "hier_int8", "ps"):
            assert abs(results[s][0] - results["allreduce"][0]) < 1e-3, (s, results)
        print("STRATEGIES_OK", results)
        """,
        prelude=f"MESH_SHAPE = {mesh_shape!r}\n",
    )
    assert "STRATEGIES_OK" in out


@pytest.mark.slow
@pytest.mark.parametrize("mesh_shape", POD_MESHES, ids=lambda s: "x".join(map(str, s)))
def test_multi_pod_grads_match_single_device(mesh_shape):
    """Gradient math is mesh-invariant: a 2-pod hier sync over the same
    global batch reproduces the single-device update.

    Runs on the four-chip host layouts (see ``POD_MESHES``): 2x2x2 trips
    XLA's CPU partitioner only."""
    out = run_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_mesh
        from repro.launch.shapes import params_specs
        from repro.models import init_params, loss_fn
        from repro.distributed import make_train_step, init_train_state
        from repro.optim import AdamWConfig, adamw_update, init_adamw

        cfg = get_smoke_config("olmo-1b")
        key = jax.random.PRNGKey(7)
        B, S = 8, 16
        toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
        batch = {"tokens": toks, "labels": toks}
        params = init_params(key, cfg)

        # single-device reference (loss averaged over the global batch)
        (_, _), g_ref = jax.value_and_grad(lambda p: loss_fn(p, batch, cfg), has_aux=True)(params)
        opt = AdamWConfig(warmup_steps=1)
        p_ref, _, _ = adamw_update(opt, g_ref, init_adamw(params), params)

        mesh = make_mesh(MESH_SHAPE, ("pod", "data", "model"))
        p_shapes = params_specs(cfg)
        b_shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch)
        with mesh:
            step, _ = make_train_step(cfg, mesh, opt_cfg=opt, strategy="hier",
                                      params_shapes=p_shapes, batch_shapes=b_shapes,
                                      donate=False)
            state = init_train_state(params, opt, strategy="hier")
            p_out, _, m = step(params, state, batch)
        diffs = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), p_ref, p_out)
        worst = max(jax.tree.leaves(diffs))
        assert worst < 2e-5, f"max param divergence {worst}"
        print("MESH_INVARIANT_OK", worst)
        """,
        prelude=f"MESH_SHAPE = {mesh_shape!r}\n",
    )
    assert "MESH_INVARIANT_OK" in out
