"""The held-expert cell's own files: ``moe_counts.py``, the four readers of
its per-layer metrics, and its reference through the harness.

* the counts against arithmetic by hand at the cell's sizes;
* the readers on hand-made device events under the layer's named scopes,
  and nothing read from an untraced run;
* the cell, cut to a CPU size, runs through ``harness.execute`` against
  ``references/mellum2-12b-a2.5b-l4e8.py`` and reads correct; with half the
  tokens' expert pairs dropped, or its state unchanged, it does not.
"""

import json
import sys
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from test_chip_faults import _wrap_step  # noqa: E402
from tiny_cells import cpu_as_chip  # noqa: E402

from benchmarks.chip import harness, moe_counts, spans  # noqa: E402
from benchmarks.chip import trace as tr  # noqa: E402

CELL = "mellum2-12b-a2.5b-l4e8.train.b2x8192"
MODEL = json.loads((harness.HERE / "configs" / "mellum2-12b-a2.5b-l4e8.json").read_text())["model"]
TRAFFIC = json.loads((harness.HERE / "traffic" / "train.b2x8192.json").read_text())
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1e6


def test_counts_by_hand():
    tokens = 2 * 8192
    pairs = tokens * 8 * 8 / 64  # a token's 8 choices fall on the 8 held of 64 at their share
    assert moe_counts.expected_pairs(MODEL, tokens) == 4 * pairs == 65536
    assert moe_counts.expert_train_flops(MODEL, tokens) == 4 * pairs * 3 * 2 * 3 * 2304 * 896
    attention = 2304 * 4096 * 2 + 2 * 2304 * 512  # wq and wo; wk and wv
    layer = attention + 2304 * 64 + 2 * 2304 + 3 * 2304 * 896  # router, norms, one expert's worth
    n = 2 * 12288 * 2304 + 2304 + 4 * layer  # untied embedding and head, final norm
    assert moe_counts.active_params(MODEL) == n == 166_940_928
    per_token = 6 * n + 12 * 32 * 128 * (3 * 1024 + 8192)  # three windows of 1024, one full layer
    assert moe_counts.train_flops_per_token(MODEL, 8192) == per_token
    assert per_token == pytest.approx(1.5553e9, rel=1e-4)


def _program():
    """Two runs of the step program, (0, 10) and (15, 25), with the layer's
    scopes inside the ffn of a scanned, rematted block."""
    base = "jit(inner)/transpose(jvp(layers))/while/body/checkpoint/ffn"
    step_ops = [
        (f"{base}/moe_router/dot_general", 0, 1),
        (f"{base}/moe_dispatch/sort", 1, 2),
        (f"{base}/moe_dispatch/gather", 2, 2.5),
        (f"{base}/moe_experts/gmm", 2.5, 6),
        (f"{base}/moe_combine/reduce", 6, 7),
        ("jit(inner)/jvp(attention)/flash/splash", 7, 9),
        ("jit(inner)/adamw/mul", 9, 10),
    ]
    paths = [(p, (s + k) * MS, (e + k) * MS) for k in (0, 15) for p, s, e in step_ops]
    dev = tr.Device("/device:TPU:0", ops=[("op", s, e) for _, s, e in paths],
                    modules=[("jit_inner(1)", 0, 10 * MS), ("jit_inner(1)", 15 * MS, 25 * MS)])
    return spans.Program(host=[], devices=[dev], scopes={dev.name: paths})


def _rec(**kw):
    return types.SimpleNamespace(trace=object(), window_ns=(0, 26 * MS), model=MODEL, traffic=TRAFFIC,
                                 chips=1, peak=PEAK, **kw)


def test_readers_on_hand_made_events(monkeypatch):
    monkeypatch.setattr(spans, "of", lambda rec: _program())
    rec = _rec()
    assert harness.reader("moe_experts_ms.train")(rec) == pytest.approx(3.5)
    assert harness.reader("moe_route_ms.train")(rec) == pytest.approx(1 + 1.5 + 1)
    flops = moe_counts.expert_train_flops(MODEL, 2 * 8192)
    assert harness.reader("moe_experts_roofline.train")(rec) == pytest.approx(100 * flops / 3.5e-3 / 197e12)


def test_step_share_reads_the_window():
    rec = _rec(window={"tokens_per_s": 40_000.0})
    want = 100 * moe_counts.train_flops_per_token(MODEL, 8192) * 40_000 / 197e12
    assert harness.reader("step_mfu_moe.train")(rec) == pytest.approx(want)
    assert 30 < want < 33


def test_trace_readers_read_nothing_untraced():
    rec = types.SimpleNamespace(trace=None, window_ns=None, model=MODEL, traffic=TRAFFIC, chips=1, peak=PEAK)
    for name in ("moe_experts_ms.train", "moe_route_ms.train", "moe_experts_roofline.train"):
        assert harness.reader(name)(rec) is None


def _tiny_spec():
    spec = harness.cell_spec(CELL)
    m = spec["config"]["model"]
    m.update(d_model=64, num_heads=8, num_kv_heads=2, head_dim=16, d_ff=32, vocab_size=128,
             local_window=8, dtype="float32")
    m["moe"].update(num_experts=16, num_experts_per_tok=4, held=4, first_held=4)
    m["yarn"].update(original_max_position_embeddings=16, factor=4.0, attention_factor=None)
    spec["traffic"].update(global_batch=2, seq_len=32, warmup_steps=4, pool_batches=4)
    return spec


def _drop_half_the_tokens(monkeypatch):
    from repro.models import ffn

    combine = ffn._combine

    def dropping(ys, w, slot, held, pair):
        return combine(ys, w.at[w.shape[0] // 2 :].set(0.0), slot, held, pair)

    monkeypatch.setattr(ffn, "_combine", dropping)


@pytest.mark.parametrize("fault,correct", [(None, True), ("tokens_dropped", False), ("state_unchanged", False)])
def test_the_cell_at_a_cpu_size_against_its_reference(fault, correct, monkeypatch):
    import jax

    spec = _tiny_spec()
    cpu_as_chip(monkeypatch)
    if fault == "tokens_dropped":
        _drop_half_the_tokens(monkeypatch)
    elif fault:
        _wrap_step(monkeypatch, fault)
    out = harness.execute(spec, jax.devices()[:1], 2**31 + 11, 0.3, False, 0.0)
    assert out["correct"] is correct, out["checks"]
    assert set(out["metrics"]) == {"setup_s", "train_tokens_per_s"}
    if fault is None:  # f32 at this size: the reference to rounding
        assert out["checks"]["grad_gap"]["value"] < 1e-4
