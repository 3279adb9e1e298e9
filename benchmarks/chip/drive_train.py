"""Training cells: ``GeoTrainer.run`` timed at its ``on_step`` hook.

The trainer is built as ``repro.launch.train`` builds it: a host mesh
(``pods`` > 1 puts the pods on the ``pod`` axis), a ``GeoFabric`` and a
``TrainerConfig``.  Three things are the benchmark's own:

* its loader, a pool of distinct batches drawn from the seed, replaces the
  trainer's synthetic corpus (whose sampler would be timed otherwise);
* the starting state: the benchmark's weights from the seed, and the
  optimizer state the program's ``init_train_state`` makes for them;
* the end: ``on_step`` raises once the window has closed.

The first ``warmup_steps`` steps are set-up; the window holds every step
that ends within ``--seconds`` after them.  The first ``checked_steps``
steps go through the same loop, call and feed as the window; the
program's loss of each, the norms of the first clipped gradient (from
AdamW's first moment after one step) and the norms of the parameters'
change over those steps are kept for the comparison with the reference.
"""

from __future__ import annotations

import gc
import math
import time
from typing import Dict, List

from . import inputs, reference
from .checks import train_numbers
from .harness import fresh_dir, log, memory_peak


class WindowClosed(Exception):
    """Raised from ``on_step`` to end the run once the window has closed."""


class PoolLoader:
    """The trainer's ``loader``: cycles through a pool of host batches."""

    def __init__(self, pool: List[Dict]):
        self.pool = pool
        self.step = 0

    def next_batch(self) -> Dict:
        import jax

        with jax.profiler.TraceAnnotation("bench.loader"):
            batch = self.pool[self.step % len(self.pool)]
            self.step += 1
            return batch


def model_config(cfg_file: Dict):
    from repro.models.config import ModelConfig

    m = dict(cfg_file["model"])
    m["pattern"] = tuple(m["pattern"])
    return ModelConfig(**m)


def weights_fn(cfg, out_shardings=None):
    """``seed -> weights``, one jitted call that the reference can repeat."""
    from repro.launch.shapes import params_specs

    return inputs.weights_builder(params_specs(cfg), out_shardings)


def run(spec: Dict, devs, *, seed: int, seconds: float, tracer, t_process: float, mark) -> Dict:
    import jax

    from repro.core.geo import GeoFabric
    from repro.distributed import init_train_state
    from repro.launch.compile_cache import CompileStats
    from repro.launch.mesh import make_host_mesh, make_mesh
    from repro.optim import AdamWConfig
    from repro.runtime import GeoTrainer, TrainerConfig

    t = spec["traffic"]
    cfg = model_config(spec["config"])
    pods = t.get("pods", 1)
    if len(jax.devices()) == len(devs):
        mesh = make_host_mesh(pods=pods, model=1) if pods > 1 else make_host_mesh()
    else:
        shape, axes = ((pods, len(devs) // pods, 1), ("pod", "data", "model")) if pods > 1 else ((len(devs), 1), ("data", "model"))
        mesh = make_mesh(shape, axes, devices=devs)
    small_seed = int(inputs.seed_words(seed)[0])
    opt = AdamWConfig(**t["adamw"])
    trainer = GeoTrainer(
        cfg, mesh,
        trainer_cfg=TrainerConfig(
            seq_len=t["seq_len"], global_batch=t["global_batch"], steps=10**9,
            strategy=t["strategy"], num_channels=t["num_channels"],
            checkpoint_every=10**9, seed=small_seed, opt=opt,
        ),
        checkpoint_dir=str(fresh_dir("ckpt")),
        geo=GeoFabric(num_pods=max(pods, 2), workers_per_pod=2, seed=small_seed),
    )
    mark("trainer")
    pool = inputs.train_pool(seed, cfg.vocab_size, t["pool_batches"], t["global_batch"], t["seq_len"])
    trainer.loader = PoolLoader(pool)
    make_params = weights_fn(cfg, trainer.shardings["params"])

    def start():
        mark("pool")
        params = make_params(seed)
        state = init_train_state(params, opt, strategy=t["strategy"])
        state = jax.device_put(state, trainer.shardings["state"])
        mark("weights")
        return params, state, 0

    trainer.init_or_restore = start

    # -- capture the checked steps' readings through the loop's own call -----
    checked = t["checked_steps"]
    first_moment = jax.jit(lambda m: reference.leaf_norms(jax.tree.map(lambda a: a / (1 - opt.b1), m)))
    readings: Dict = {}
    program_step = trainer.step_fn
    calls = [0]

    def capturing_step(params, state, batch):
        out = program_step(params, state, batch)
        calls[0] += 1
        if calls[0] == 1:
            readings["grad"] = first_moment(out[1].adam.m)
        if calls[0] == checked:
            readings["change"] = reference.change_norms(out[0], make_params(seed))
            trainer.step_fn = program_step
        return out

    trainer.step_fn = capturing_step

    # -- the window ------------------------------------------------------------
    warmup = t["warmup_steps"]
    tokens_per_step = t["global_batch"] * t["seq_len"]
    losses: List[float] = []
    clock = {"start": None, "last": None, "steps": 0}
    compiles = CompileStats()

    def on_step(step: int, row: Dict) -> None:
        losses.append(row["loss"])
        now = time.perf_counter()
        if step == 0:
            mark("first_step")
        if step + 1 == warmup:
            tracer.start()
            compiles.__enter__()
            clock["start"] = clock["last"] = time.perf_counter()
        elif clock["start"] is not None:
            if now - clock["start"] > seconds:
                raise WindowClosed
            clock["last"] = now
            clock["steps"] += 1

    with CompileStats() as setup_compiles:
        try:
            trainer.run(on_step=on_step)
        except WindowClosed:
            pass
    compiles.__exit__(None, None, None)
    tracer.stop()
    peak = memory_peak(devs)
    window_s = clock["last"] - clock["start"]
    steps = clock["steps"]
    window = {
        "seconds": window_s, "steps": steps, "tokens": steps * tokens_per_step,
        "tokens_per_s": steps * tokens_per_step / window_s,
    }
    program = {
        "losses": losses[:checked],
        "grad": reference.to_host(readings["grad"]),
        "change": reference.to_host(readings["change"]),
    }
    nonfinite = sum(not math.isfinite(x) for x in losses[warmup:])
    del trainer, program_step, capturing_step, readings
    gc.collect()

    # -- the reference, after the window and with the program's state freed ---
    t_ref = time.perf_counter()
    ref_weights = weights_fn(cfg)
    ref_of = reference.lookup(spec["cell"]["config"])
    ref_losses, ref_grad, ref_params = ref_of.train_steps(
        ref_weights(seed), pool[:checked], spec["config"]["model"], t["adamw"],
        strategy=t["strategy"], pods=pods, precision="f32", rows_per_block=t["ref_rows_per_block"],
        devices=devs,
    )
    ref = {
        "losses": ref_losses,
        "grad": reference.to_host(ref_grad),
        "change": reference.to_host(reference.change_norms(ref_params, ref_weights(seed))),
    }
    numbers = train_numbers(program, ref)
    info = {
        "setup_compiles": f"{setup_compiles.hits} hits / {setup_compiles.misses} misses, "
                          f"{setup_compiles.compile_s:.2f}s",
        "window_compiles": compiles.hits + compiles.misses,
        "losses": [f"{a:.7f}/{b:.7f}" for a, b in zip(program["losses"], ref["losses"])],
        "reference": ref_of.source,
        "reference_s": f"{time.perf_counter() - t_ref:.2f}",
    }
    log(f"window {steps} steps in {window_s:.3f}s; set-up {clock['start'] - t_process:.2f}s")
    return {
        "end_to_end": {
            "setup_s": clock["start"] - t_process,
            "train_tokens_per_s": window["tokens_per_s"],
        },
        "window": window,
        "numbers": numbers,
        "attempted": steps,
        "failed": nonfinite,
        "memory_peak_bytes": peak,
        "info": info,
    }

