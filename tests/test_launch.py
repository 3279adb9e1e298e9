"""Launch-layer units: HLO collective parsing, shapes/specs, mesh helpers."""

import jax
import pytest

from repro.configs import get_config
from repro.launch.hlo_stats import parse_collectives, shape_bytes
from repro.launch.mesh import batch_axes, chips_per_pod, num_pods
from repro.launch.shapes import SHAPES, decode_cache_specs, input_specs, params_specs


class TestShapeBytes:
    @pytest.mark.parametrize(
        "s,expected",
        [
            ("f32[128,1024]{1,0}", 128 * 1024 * 4),
            ("bf16[2,3,4]", 48),
            ("s8[100]", 100),
            ("pred[16]", 16),
            ("f32[]", 4),
            ("(f32[8], bf16[8])", 8 * 4 + 8 * 2),
        ],
    )
    def test_sizes(self, s, expected):
        assert shape_bytes(s) == expected


class TestParseCollectives:
    HLO = """
  %ag = f32[64,128]{1,0} all-gather(f32[4,128] %x), replica_groups={{0,1},{2,3}}, dimensions={0}
  %ar = bf16[256]{0} all-reduce(bf16[256] %y), replica_groups=[2,256]<=[512], to_apply=%add
  %rs = f32[32]{0} reduce-scatter(f32[64] %z), replica_groups={{0,256}}, dimensions={0}
  %dot = f32[8,8] dot(f32[8,8] %a, f32[8,8] %b)
"""

    def test_counts_and_bytes(self):
        stats = parse_collectives(self.HLO)
        assert stats.count == 3
        assert stats.bytes_by_kind["all-gather"] == 64 * 128 * 4
        assert stats.bytes_by_kind["all-reduce"] == 256 * 2
        assert stats.bytes_by_kind["reduce-scatter"] == 32 * 4

    def test_cross_pod_classification(self):
        stats = parse_collectives(self.HLO, pod_size=256)
        # explicit {{0,256}} spans pods; {{0,1},{2,3}} does not;
        # iota [2,256]<=[512] groups of 256 stay within a pod
        assert stats.cross_pod_bytes == 32 * 4

    def test_iota_oversized_group_is_cross_pod(self):
        hlo = "%ar = f32[16] all-reduce(f32[16] %x), replica_groups=[1,512]<=[512]"
        stats = parse_collectives(hlo, pod_size=256)
        assert stats.cross_pod_bytes == 64

    def test_transposed_iota_pairs_across_pods(self):
        """[256,2]<=[2,256]T(1,0): groups pair device i with i+256 — the
        form GSPMD emits for manual-pod psums on the 2x16x16 mesh."""
        hlo = "%ar = f32[16] all-reduce(f32[16] %x), replica_groups=[256,2]<=[2,256]T(1,0)"
        stats = parse_collectives(hlo, pod_size=256)
        assert stats.cross_pod_bytes == 64
        assert stats.unclassified_bytes == 0


class TestInputSpecs:
    def test_train_specs_for_every_arch(self):
        for arch in ("olmo-1b", "rwkv6-7b", "phi-3-vision-4.2b", "musicgen-large"):
            cfg = get_config(arch)
            specs = input_specs(cfg, "train_4k")["batch"]
            # every leaf is an allocation-free ShapeDtypeStruct with the
            # assigned global batch / seq
            for leaf in jax.tree.leaves(specs):
                assert isinstance(leaf, jax.ShapeDtypeStruct)
                assert leaf.shape[0] == SHAPES["train_4k"].global_batch
            if cfg.frontend == "none":
                assert specs["tokens"].shape == (256, 4096)

    def test_decode_specs(self):
        cfg = get_config("olmo-1b")
        specs = input_specs(cfg, "decode_32k")
        assert specs["tokens_t"].shape == (128,)
        cache = decode_cache_specs(cfg, "decode_32k")
        k = cache["groups"]["slot0"]["k"]
        assert k.shape == (16, 128, 32768, 16, 128)  # (L, B, S, KVH, hd)

    def test_long_500k_rejected_for_full_attn(self):
        with pytest.raises(ValueError, match="quadratic"):
            input_specs(get_config("yi-34b"), "long_500k")

    def test_long_500k_state_is_o1_for_rwkv(self):
        cfg = get_config("rwkv6-7b")
        cache = decode_cache_specs(cfg, "long_500k")
        total = sum(s.size for s in jax.tree.leaves(cache))
        # recurrent state is independent of the 524288 context length
        assert total < 50e6

    def test_params_specs_no_allocation(self):
        specs = params_specs(get_config("arctic-480b"))  # 477B params, no memory
        n = sum(s.size for s in jax.tree.leaves(specs))
        assert n > 4e11


class TestServeCli:
    """ISSUE 8 satellite: serve.py's batch construction now lives in
    ``repro.launch.batches`` and is shared with the serving request
    model — the CLI must keep working through the shared helper."""

    def test_serve_smoke(self, capsys):
        from repro.launch import serve

        serve.main(
            ["--arch", "distilgpt2-82m", "--batch", "2", "--prompt-len", "8",
             "--gen", "2"]
        )
        out = capsys.readouterr().out
        assert "prefill: 2x8" in out
        assert "decode: 2 steps" in out
        assert "sample[0]:" in out

    def test_synthetic_prompt_batch_shapes(self):
        from repro.launch.batches import synthetic_prompt_batch

        cfg = get_config("distilgpt2-82m")
        key = jax.random.PRNGKey(0)
        batch = synthetic_prompt_batch(cfg, key, 2, 8)
        assert batch["tokens"].shape == (2, 8)
        # deterministic in the key
        again = synthetic_prompt_batch(cfg, key, 2, 8)
        assert (batch["tokens"] == again["tokens"]).all()

    def test_request_batch_reuses_helper(self):
        """The serving request model builds batches through the same
        helper, keyed by request id."""
        from repro.launch.batches import synthetic_prompt_batch
        from repro.serving import Request, request_batch

        cfg = get_config("distilgpt2-82m")
        req = Request(rid=7, step=0, home_dc=1, user=42, tokens=8)
        got = request_batch(cfg, req)
        want = synthetic_prompt_batch(cfg, jax.random.PRNGKey(7), 1, 8)
        assert (got["tokens"] == want["tokens"]).all()


class _FakeMesh:
    """Shape/axis view of a mesh (this process has 1 real device)."""

    def __init__(self, shape, axes):
        self.shape = dict(zip(axes, shape))
        self.axis_names = axes


class TestMeshHelpers:
    def test_mesh_math(self):
        mesh = _FakeMesh((2, 2, 2), ("pod", "data", "model"))
        assert num_pods(mesh) == 2
        assert chips_per_pod(mesh) == 4
        assert batch_axes(mesh) == ("pod", "data")
        single = _FakeMesh((4, 2), ("data", "model"))
        assert num_pods(single) == 1
        assert batch_axes(single) == ("data",)


class TestCompileCache:
    """The persistent compile cache is placed from outside, else at a fixed
    path in the checkout (never a temporary or per-process one)."""

    @pytest.fixture(autouse=True)
    def _restore_config(self):
        names = ("jax_compilation_cache_dir", "jax_compilation_cache_include_metadata_in_key",
                 "jax_hlo_source_file_canonicalization_regex")
        saved = {n: getattr(jax.config, n) for n in names}
        yield
        for n, v in saved.items():
            jax.config.update(n, v)

    def test_key_holds_the_programs_scopes_and_checkout_relative_sources(self, monkeypatch, tmp_path):
        import re

        from repro.launch.compile_cache import CHECKOUT, enable_compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        enable_compile_cache()
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        pattern = jax.config.jax_hlo_source_file_canonicalization_regex
        assert re.sub(pattern, "", str(CHECKOUT / "src" / "repro" / "models" / "transformer.py")) == \
            "src/repro/models/transformer.py"
        assert re.sub(pattern, "", "/elsewhere/src/repro/models/transformer.py").startswith("/elsewhere")

    def test_env_dir_is_left_to_jax(self, monkeypatch, tmp_path):
        from repro.launch.compile_cache import enable_compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_dir_is_fixed_in_checkout(self, monkeypatch):
        from pathlib import Path

        from repro.launch.compile_cache import enable_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        saved = jax.config.jax_compilation_cache_dir
        try:
            path = enable_compile_cache()
            assert path == str(Path(__file__).resolve().parents[1] / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
        finally:
            jax.config.update("jax_compilation_cache_dir", saved)

    def test_compile_stats_count_backend_compiles(self):
        from repro.launch.compile_cache import CompileStats

        with CompileStats() as stats:
            jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7.0)).block_until_ready()
        assert stats.compile_s > 0
        assert "compile" in stats.summary()
