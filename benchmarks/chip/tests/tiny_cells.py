"""Cells of the benchmark cut to a size the CPU runs in seconds.

The widths shrink and the compute dtype is float32, so a sound program
agrees with the reference to rounding; the limits are the cells' own.
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import harness  # noqa: E402

TRAIN = "distilgpt2-82m.train.b8x1024"
TRAIN4 = "distilgpt2-82m.train4.hier_int8.b32x1024"
SERVE = "distilgpt2-82m.serve.b128.p512g64"
OLMO = "olmo-1b-l4.train.b4x2048"
_cell_spec = harness.cell_spec


def tiny_spec(cell: str) -> dict:
    spec = _cell_spec(cell)
    t = spec["traffic"]
    if t["kind"] == "train":
        spec["config"]["model"].update(
            num_layers=2, d_model=32, num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
            vocab_size=128, dtype="float32",
        )
        t.update(global_batch=4 * t.get("pods", 1), seq_len=16, warmup_steps=4, pool_batches=4)
    else:  # wide enough that the logits spread as at full width
        spec["config"]["model"].update(
            num_layers=2, d_model=256, num_heads=4, num_kv_heads=4, head_dim=64, d_ff=512,
            vocab_size=512, dtype="float32",
        )
        t.update(batch=32, prompt_len=16, gen_tokens=32, warmup_batches=1, sample_requests=32)
    return spec


def cpu_as_chip(monkeypatch) -> None:
    """Let the harness take the CPU for a chip: devices and peaks, and no
    persistent compile cache (it would outlive the test)."""
    import jax

    from benchmarks.chip import counts

    monkeypatch.setitem(counts.PEAKS, jax.devices()[0].device_kind, counts.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(harness, "chips", lambda n: jax.devices()[:n])
    monkeypatch.setattr(harness, "enable_cache", lambda: "off")
