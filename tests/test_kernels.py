"""Pallas kernel sweeps: shapes x dtypes vs pure-jnp oracles (interpret mode).

The kernels and their wrappers default to the compiled TPU path, so every
call here passes ``interpret=True``; ``tests/test_tpu_compile.py`` compiles
the same kernels for a described v5e chip.

Per the assignment: every kernel sweeps shapes/dtypes and asserts allclose
against its ref.py oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention, flash_attention_fwd, flash_attention_ref
from repro.kernels.rwkv6_wkv import wkv6, wkv6_fwd, wkv6_ref
from repro.kernels.wan_quant import (
    dequantize,
    quantize,
    wan_dequant,
    wan_dequant_ref,
    wan_quant,
    wan_quant_ref,
)

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _qkv(key, b, sq, sk, h, kvh, hd, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, h, sq, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (b, kvh, sk, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (b, kvh, sk, hd)).astype(dtype)
    return q, k, v


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "b,s,h,kvh,hd,bq,bk",
        [
            (1, 128, 1, 1, 64, 128, 128),
            (2, 256, 4, 2, 64, 128, 128),
            (2, 256, 8, 1, 128, 128, 256),  # MQA, rectangular blocks
            (1, 512, 4, 4, 128, 256, 128),
        ],
    )
    def test_causal_sweep(self, dtype, b, s, h, kvh, hd, bq, bk):
        q, k, v = _qkv(jax.random.PRNGKey(0), b, s, s, h, kvh, hd, dtype)
        out = flash_attention_fwd(q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True)
        ref = flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), **TOL[dtype]
        )

    @pytest.mark.parametrize("window", [32, 64, 128])
    def test_sliding_window(self, window):
        q, k, v = _qkv(jax.random.PRNGKey(1), 2, 256, 256, 4, 2, 64, jnp.float32)
        out = flash_attention_fwd(
            q, k, v, causal=True, window=window, block_q=128, block_k=128, interpret=True
        )
        ref = flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_non_causal(self):
        q, k, v = _qkv(jax.random.PRNGKey(2), 1, 256, 256, 2, 2, 64, jnp.float32)
        out = flash_attention_fwd(q, k, v, causal=False, block_q=128, block_k=128, interpret=True)
        ref = flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_softcap(self):
        q, k, v = _qkv(jax.random.PRNGKey(3), 1, 128, 128, 2, 1, 64, jnp.float32)
        out = flash_attention_fwd(
            q, k, v, causal=True, logit_softcap=30.0, block_q=128, block_k=128, interpret=True
        )
        ref = flash_attention_ref(q, k, v, causal=True, logit_softcap=30.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_cross_attention_lengths(self):
        """Sq != Sk (prefill extending an existing cache)."""
        q, k, v = _qkv(jax.random.PRNGKey(4), 1, 128, 384, 2, 2, 64, jnp.float32)
        out = flash_attention_fwd(q, k, v, causal=False, block_q=128, block_k=128, interpret=True)
        ref = flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_ops_wrapper_model_layout(self):
        """[B, S, H, hd] wrapper matches the model's sdpa on the same mask."""
        from repro.models.attention import sdpa

        b, s, h, kvh, hd = 2, 256, 4, 2, 64
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q = jax.random.normal(ks[0], (b, s, h, hd))
        k = jax.random.normal(ks[1], (b, s, kvh, hd))
        v = jax.random.normal(ks[2], (b, s, kvh, hd))
        out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128, interpret=True)
        pos = jnp.arange(s)
        ref = sdpa(q, k, v, q_positions=pos, k_positions=pos, impl="naive")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_fallback_tiny_shapes(self):
        """Non-tileable shapes raise instead of silently running the reference."""
        q, k, v = _qkv(jax.random.PRNGKey(6), 1, 48, 48, 2, 2, 32, jnp.float32)
        swap = lambda x: jnp.swapaxes(x, 1, 2)
        with pytest.raises(ValueError, match="no 128-multiple block"):
            flash_attention(swap(q), swap(k), swap(v), causal=True, interpret=True)


class TestWanQuant:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("rows,lanes,rt", [(8, 256, 8), (64, 512, 32), (256, 1024, 256), (13, 256, 13)])
    def test_sweep_vs_ref(self, dtype, rows, lanes, rt):
        x = (jax.random.normal(jax.random.PRNGKey(rows), (rows, lanes)) * 5).astype(dtype)
        xf = x.astype(jnp.float32)
        q_k, s_k = wan_quant(xf, row_tile=rt, interpret=True)
        q_r, s_r = wan_quant_ref(xf)
        # scale division can differ by 1 ULP between kernel and ref, which
        # flips round-to-even on exact .5 boundaries -> allow |dq| <= 1 on
        # a vanishing fraction of lanes, exact everywhere else.
        dq = np.abs(np.asarray(q_k, np.int32) - np.asarray(q_r, np.int32))
        assert dq.max() <= 1
        assert (dq != 0).mean() < 1e-3
        np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), rtol=1e-6)

    def test_dequant_matches_ref(self):
        x = jax.random.normal(jax.random.PRNGKey(9), (32, 512))
        q, s = wan_quant_ref(x)
        back_k = wan_dequant(q, s, row_tile=32, interpret=True)
        back_r = wan_dequant_ref(q, s)
        np.testing.assert_allclose(np.asarray(back_k), np.asarray(back_r), rtol=1e-6)

    def test_roundtrip_error_bound(self):
        x = jax.random.normal(jax.random.PRNGKey(10), (64, 1024)) * 3
        q, s = wan_quant(x, row_tile=64, interpret=True)
        back = wan_dequant(q, s, row_tile=64, interpret=True)
        blocks = x.reshape(64, 4, 256)
        bound = jnp.abs(blocks).max(-1) / 127.0 * 0.5 + 1e-7
        err = jnp.abs(back - x).reshape(64, 4, 256).max(-1)
        assert bool((err <= bound * 1.01).all())

    def test_matches_distributed_compression(self):
        """The kernel and the sync-path jnp compressor agree bit-for-bit."""
        from repro.distributed.compression import int8_compress

        x = jax.random.normal(jax.random.PRNGKey(11), (16, 512))
        c = int8_compress(x)
        q_k, s_k = wan_quant(x, row_tile=16, interpret=True)
        np.testing.assert_array_equal(np.asarray(q_k), np.asarray(c.values))
        np.testing.assert_allclose(np.asarray(s_k), np.asarray(c.scales), rtol=1e-6)


    @pytest.mark.parametrize("rows,rt", [(13, 1), (64, 16), (96, 64)])
    def test_illegal_row_tile_raises(self, rows, rt):
        """A row tile the TPU cannot tile is refused, not silently run."""
        x = jnp.ones((rows, 256), jnp.float32)
        with pytest.raises(ValueError, match="row tile"):
            wan_quant(x, row_tile=rt, interpret=True)

    @pytest.mark.parametrize(
        "shape", [(50257, 768), (6, 768, 2304), (3, 70), (300,), (), (4, 50257)]
    )
    def test_ops_pad_rows_and_lanes(self, shape):
        """quantize/dequantize pad any leaf to legal tiles and strip the padding.

        ``(4, 50257)`` is a vocabulary-wide row: 197 scale blocks, walked in
        one 128-block lane group and one that overhangs the row.
        """
        x = jax.random.normal(jax.random.PRNGKey(12), shape) * 2
        q, s = quantize(x, interpret=True)
        rows = x.reshape(1, -1) if x.ndim < 2 else x.reshape(-1, x.shape[-1])
        assert q.shape[0] == s.shape[0] == rows.shape[0]
        pad = (-rows.shape[-1]) % 256
        q_r, s_r = wan_quant_ref(jnp.pad(rows, ((0, 0), (0, pad))))
        assert int(jnp.abs(q.astype(jnp.int32) - q_r.astype(jnp.int32)).max()) <= 1
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_r), rtol=1e-6)
        back = dequantize(q, s, orig_shape=tuple(x.shape), interpret=True)
        assert back.shape == x.shape
        bound = float(jnp.abs(x).max()) / 254.0 * 1.01 + 1e-7
        assert float(jnp.abs(back - x).max()) <= bound


class TestWkv6:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("b,t,h,n,chunk", [(1, 32, 1, 8, 8), (2, 64, 3, 16, 16), (2, 128, 2, 64, 32)])
    def test_sweep_vs_ref(self, dtype, b, t, h, n, chunk):
        ks = jax.random.split(jax.random.PRNGKey(t), 6)
        r = (jax.random.normal(ks[0], (b, t, h, n)) * 0.5).astype(dtype)
        k = (jax.random.normal(ks[1], (b, t, h, n)) * 0.5).astype(dtype)
        v = (jax.random.normal(ks[2], (b, t, h, n)) * 0.5).astype(dtype)
        w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h, n)) + 2.0).astype(dtype)
        u = (jax.random.normal(ks[4], (h, n)) * 0.1).astype(jnp.float32)
        s0 = jax.random.normal(ks[5], (b, h, n, n)) * 0.1
        out_k, fin_k = wkv6_fwd(r, k, v, w, u, s0, chunk=chunk, interpret=True)
        out_r, fin_r = wkv6_ref(r, k, v, w, u, s0)
        tol = dict(rtol=1e-4, atol=1e-4) if dtype == jnp.float32 else dict(rtol=5e-2, atol=5e-2)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), **tol)
        np.testing.assert_allclose(np.asarray(fin_k), np.asarray(fin_r), **tol)

    def test_state_carries_across_chunks(self):
        """Running T in one chunk == two chunks of T/2 (state continuity)."""
        b, t, h, n = 1, 64, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        r, k, v = (jax.random.normal(ks[i], (b, t, h, n)) * 0.5 for i in range(3))
        w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h, n)) + 2.0)
        u = jax.random.normal(ks[4], (h, n)) * 0.1
        s0 = jnp.zeros((b, h, n, n))
        out_one, fin_one = wkv6_fwd(r, k, v, w, u, s0, chunk=64, interpret=True)
        out_two, fin_two = wkv6_fwd(r, k, v, w, u, s0, chunk=32, interpret=True)
        np.testing.assert_allclose(np.asarray(out_one), np.asarray(out_two), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(fin_one), np.asarray(fin_two), rtol=1e-5, atol=1e-5)

    def test_matches_model_wkv(self):
        """Kernel == the model stack's wkv6 scan (repro.models.rwkv6)."""
        from repro.models.rwkv6 import _wkv_with_initial_state

        b, t, h, n = 2, 32, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(1), 5)
        r, k, v = (jax.random.normal(ks[i], (b, t, h, n)) * 0.5 for i in range(3))
        w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h, n)) + 2.0)
        u = jax.random.normal(ks[4], (h, n)) * 0.1
        s0 = jnp.zeros((b, h, n, n))
        out_k, fin_k = wkv6_fwd(r, k, v, w, u, s0, chunk=16, interpret=True)
        out_m, fin_m = _wkv_with_initial_state(r, k, v, w, u, s0)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_m), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(fin_k), np.asarray(fin_m), rtol=1e-4, atol=1e-5)

    def test_ops_wrapper_default_state(self):
        """ops.wkv6 starts from a zero state and matches the reference."""
        b, t, h, n = 1, 32, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(2), 5)
        r, k, v = (jax.random.normal(ks[i], (b, t, h, n)) * 0.5 for i in range(3))
        w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h, n)) + 2.0)
        u = jax.random.normal(ks[4], (h, n)) * 0.1
        out_k, fin_k = wkv6(r, k, v, w, u, chunk=16, interpret=True)
        out_r, fin_r = wkv6_ref(r, k, v, w, u, jnp.zeros((b, h, n, n)))
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(fin_k), np.asarray(fin_r), rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("t,chunk", [(24, 12), (30, 30)])
    def test_untileable_chunk_raises(self, t, chunk):
        """A chunk that is not a multiple of 8 timesteps is refused."""
        x = jnp.ones((1, t, 1, 8))
        with pytest.raises(ValueError, match="cannot tile"):
            wkv6(x, x, x, x, jnp.ones((1, 8)), chunk=chunk, interpret=True)
