"""The output comparison fails what it must: the control, and a run whose timed
path is broken underneath.

At smoke size on the CPU (float32, so a sound program agrees with the
reference to rounding) and under each cell's own limits:

* the control, the reference computed with float8 matmul operands in the
  program's place, comes out not correct;
* a run of the harness, device check aside, comes out ``correct: false``
  when the program's step returns its state unchanged, leaves half of the
  batch out or alters its loss; and when serving alters a token, leaves
  half of the batch out or does not update its cache.  (A dropped cross-pod
  exchange: ``test_chip_pods.py``.)
"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from tiny_cells import SERVE, TRAIN, TRAIN4, cpu_as_chip, tiny_spec  # noqa: E402

from benchmarks.chip import checks, control, harness  # noqa: E402


def run_cell(cell, monkeypatch, seconds=0.3):
    import jax

    cpu_as_chip(monkeypatch)
    spec = tiny_spec(cell)
    return harness.execute(spec, jax.devices()[: spec["cell"]["chips"]], 11, seconds, False, 0.0)


@pytest.mark.parametrize("cell", [TRAIN, TRAIN4])
def test_training_control_is_not_correct(cell):
    spec = tiny_spec(cell)
    readings = control.train_readings(spec, 3)
    ok, _ = checks.judge(readings["control"], spec["limits"])
    assert not ok
    for fault in ("half_batch", "no_exchange"):
        if fault in readings:
            assert not checks.judge(readings[fault], spec["limits"])[0], fault


def test_serving_control_is_not_correct():
    spec = tiny_spec(SERVE)
    r = control.serve_readings(spec, 3, 0.1)
    limit = spec["limits"]["token_gap"]
    assert r["program"] <= limit < r["control"]


def _wrap_step(monkeypatch, fault):
    import jax.numpy as jnp

    from repro.runtime import trainer

    build = trainer.make_train_step

    def faulty_build(*args, **kwargs):
        step, shardings = build(*args, **kwargs)

        def faulty(params, state, batch):
            if fault == "half_batch":
                rows = batch["labels"].shape[0]
                labels = batch["labels"].at[rows // 2 :].set(-100)
                return step(params, state, {**batch, "labels": labels})
            new_params, new_state, metrics = step(params, state, batch)
            if fault == "state_unchanged":
                return params, state, metrics
            return new_params, new_state, {**metrics, "loss": metrics["loss"] * jnp.float32(1.01)}

        return faulty, shardings

    monkeypatch.setattr(trainer, "make_train_step", faulty_build)


def test_a_sound_training_run_is_correct(monkeypatch):
    assert run_cell(TRAIN, monkeypatch)["correct"] is True


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "loss_altered"])
def test_training_fault_is_not_correct(fault, monkeypatch):
    _wrap_step(monkeypatch, fault)
    assert run_cell(TRAIN, monkeypatch)["correct"] is False


@pytest.mark.parametrize("fault", ["token_altered", "half_batch", "cache_unchanged"])
def test_serving_fault_is_not_correct(fault, monkeypatch):
    import jax.numpy as jnp

    import repro.models as models

    prefill, decode = models.prefill, models.decode_step

    def faulty_prefill(params, batch, cfg, **kw):
        if fault == "half_batch":
            rows = batch["tokens"].shape[0] // 2
            tokens = jnp.concatenate([batch["tokens"][:rows]] * 2, axis=0)
            return prefill(params, {"tokens": tokens}, cfg, **kw)
        return prefill(params, batch, cfg, **kw)

    def faulty_decode(params, tokens, cache, cfg, position):
        logits, new_cache = decode(params, tokens, cache, cfg, position)
        if fault == "token_altered":
            logits = logits.at[:, 1].add(1e4)
        if fault == "cache_unchanged":
            new_cache = cache
        return logits, new_cache

    monkeypatch.setattr(models, "prefill", faulty_prefill)
    monkeypatch.setattr(models, "decode_step", faulty_decode)
    assert run_cell(SERVE, monkeypatch)["correct"] is False
