"""Each configuration's own reference: the lookup, a cell whose configuration
the dense reference refuses, and the dense path left as it was.

* ``reference.lookup`` takes ``references/<config name>.py`` where that file
  exists and the dense decoder where it does not;
* a tiny cell with an untied head and RMSNorm, which the program runs and
  the dense reference refuses, added to a checkout by files and entries
  alone (its reference a copy of ``tests/references/tiny-untied-rms.py``),
  runs through ``harness.execute`` and reads correct; with its step
  returning its state unchanged it reads not correct;
* for the dense configurations, the losses and gradients of the lookup's
  reference are bit for bit those of the cross-entropy and z-loss alone.
"""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from test_chip_faults import _wrap_step  # noqa: E402
from tiny_cells import OLMO, TRAIN, cpu_as_chip, tiny_spec  # noqa: E402

from benchmarks.chip import dense, harness, inputs, reference  # noqa: E402
from benchmarks.chip.drive_train import model_config, weights_fn  # noqa: E402

TEST_REFERENCES = Path(__file__).resolve().parent / "references"
BENCH_E2E = harness.load_json(harness.CHECKOUT / "BENCHMARK.json")["end_to_end"]
UNTIED = "tiny-untied-rms"


@pytest.mark.parametrize("config,own", [(UNTIED, True), ("distilgpt2-82m", False)])
def test_lookup_takes_the_configurations_own_file(config, own, monkeypatch):
    monkeypatch.setattr(reference, "REFERENCES", TEST_REFERENCES)
    ref = reference.lookup(config)
    if own:
        assert ref.source == str(TEST_REFERENCES / f"{config}.py")
        assert ref.forward is not dense.forward and ref.forward is reference.lookup(config).forward
    else:
        assert (ref.source, ref.forward) == ("dense.py", dense.forward)


def test_the_committed_configurations_run_the_dense_reference():
    for config in ("distilgpt2-82m", "olmo-1b-l4"):
        assert not (reference.REFERENCES / f"{config}.py").exists()
        assert reference.lookup(config).forward is dense.forward


def untied_checkout(root: Path) -> str:
    """A checkout that gains one cell by files and entries alone: a tiny
    configuration with an untied head and RMSNorm, its traffic and limits
    (cell 2's, cut to smoke size), its reference file, and ``BENCHMARK.json``
    entries.  Returns the cell's name."""
    import json
    import shutil

    chip, base, cell = root / "benchmarks" / "chip", tiny_spec(OLMO), f"{UNTIED}.train.tiny"
    model = {**base["config"]["model"], "name": UNTIED, "norm": "rmsnorm", "tie_embeddings": False}
    files = {
        f"configs/{UNTIED}.json": {"name": UNTIED, "model": model},
        "traffic/train.tiny.json": base["traffic"],
        f"limits/{cell}.json": base["limits"],
    }
    for name, content in files.items():
        (chip / name).parent.mkdir(parents=True, exist_ok=True)
        (chip / name).write_text(json.dumps(content))
    (chip / "references").mkdir()
    shutil.copy(TEST_REFERENCES / f"{UNTIED}.py", chip / "references")
    e2e = [{**m, "workloads": [cell]} for m in BENCH_E2E if m["name"] in ("setup_s", "train_tokens_per_s")]
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": UNTIED, "source": "test", "file": f"benchmarks/chip/configs/{UNTIED}.json",
                     "reduced": [], "why": "untied head and RMSNorm"}],
        "workloads": [{"name": cell, "config": UNTIED, "traffic": "train.tiny", "chips": 1, "why": "test"}],
        "end_to_end": e2e, "per_layer": [],
    }))
    return cell


@pytest.mark.parametrize("fault,correct", [(None, True), ("state_unchanged", False)])
def test_a_configuration_the_dense_reference_refuses_gets_a_cell(fault, correct, tmp_path, monkeypatch):
    import jax

    cell = untied_checkout(tmp_path)
    monkeypatch.setattr(harness, "CHECKOUT", tmp_path)
    monkeypatch.setattr(harness, "HERE", tmp_path / "benchmarks" / "chip")
    monkeypatch.setattr(reference, "REFERENCES", tmp_path / "benchmarks" / "chip" / "references")
    spec = harness.cell_spec(cell, tmp_path / "BENCHMARK.json")
    m = spec["config"]["model"]
    params = weights_fn(model_config(spec["config"]))(5)
    assert "unembed" in params
    with pytest.raises(ValueError, match="dense reference"):
        dense.forward(params, np.zeros((1, 4), np.int32), m, "f32")

    cpu_as_chip(monkeypatch)
    if fault:
        _wrap_step(monkeypatch, fault)
    out = harness.execute(spec, jax.devices()[:1], 2**31 + 5, 0.3, False, 0.0)
    assert out["correct"] is correct, out["checks"]
    assert set(out["metrics"]) == {"setup_s", "train_tokens_per_s"}


def _parent_loss_and_grad(params, tokens, labels, m, rows_per_block):
    """Cross-entropy and z-loss of the dense decoder, summed block by block,
    with no extra term anywhere: the reference as it was before architectures
    could bring one."""
    import jax
    import jax.numpy as jnp

    n = tokens.size

    def objective(params, tokens, labels):
        lg = dense.forward(params, tokens, m, "f32")[0]
        logz = jax.nn.logsumexp(lg, axis=-1)
        ll = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0] - logz
        ce, zz = -ll.sum(), jnp.square(logz).sum()
        return ce / n + m["z_loss"] * zz / n, (ce, zz)

    step = jax.jit(jax.grad(objective, has_aux=True))
    grads, ce, zz = None, 0.0, 0.0
    for r in range(0, tokens.shape[0], rows_per_block):
        g, (c, z) = step(params, tokens[r : r + rows_per_block], labels[r : r + rows_per_block])
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        ce, zz = ce + float(c), zz + float(z)
    return ce / n + m["z_loss"] * zz / n, grads


@pytest.mark.parametrize("cell", [TRAIN, OLMO])
def test_dense_losses_and_gradient_norms_are_bit_for_bit(cell):
    import jax

    spec = tiny_spec(cell)
    m, t = spec["config"]["model"], spec["traffic"]
    cfg = model_config(spec["config"])
    params = jax.tree.map(lambda a: a.astype("float32"), weights_fn(cfg)(9))
    batch = inputs.train_pool(9, cfg.vocab_size, 1, t["global_batch"], t["seq_len"])[0]
    want_loss, want_grad = _parent_loss_and_grad(params, batch["tokens"], batch["labels"], m, 2)
    loss, grad = reference.lookup(spec["cell"]["config"]).loss_and_grad(
        params, batch["tokens"], batch["labels"], m, "f32", 2)
    assert loss == want_loss
    got, want = reference.to_host(reference.leaf_norms(grad)), reference.to_host(reference.leaf_norms(want_grad))
    assert got == want
