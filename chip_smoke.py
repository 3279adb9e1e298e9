"""Chip smoke test: the system's main path, end to end, on a TPU.

    python chip_smoke.py               # one chip: train, serve, kernels
    python chip_smoke.py --four-chips  # four chips: 2-pod training, five
                                       # sync strategies vs one chip

One chip (no arguments):

* **train** — distilgpt2-82m at full width, global batch 8 x 1024 tokens,
  5 steps of the ``hier`` strategy, through ``repro.launch.train.main``.
  Every loss is finite, step 0's loss matches a plain ``loss_fn`` on the
  same parameters and batch, and the checkpoint the run wrote restores to
  the final parameters and optimizer state bit for bit.
* **serve** — prefill 8 x 512 and 64 greedy decode steps through
  ``repro.launch.serve.main``.  Tokens are in the vocabulary, and the last
  decode step's logits match a full forward pass over the generated
  sequence.
* **kernels** — the ``wan_quant`` / ``wan_dequant`` Pallas kernels,
  compiled, against ``ref.py``: on a distilgpt2 MLP leaf, and on a
  vocabulary-wide row (768 x 50257), which the kernels walk in two lane
  groups, the second overhanging the row.

Four chips (``--four-chips``): distilgpt2-82m at full width on
``make_host_mesh(pods=2)`` = (pod=2, data=2, model=1), 3 steps each of
``allreduce``, ``hier``, ``hier_int8``, ``ps`` and ``local_sgd`` through
``repro.launch.train.main``, against a one-chip run on the same global
batch.  Every strategy's step-0 loss, and every step's loss of the four
strategies that sync the pods each step, lies within ``LOSS_RTOL``
relative of one chip.  ``local_sgd`` exchanges nothing between the pods
before its first outer sync (every 8 steps), so over 3 steps each pod
trains on its own half of the batch: it is the control, and its losses
after step 0 must lie *outside* ``LOSS_RTOL``, which shows the bound
catches a run whose cross-pod exchange is missing.

Timings (compile apart from run), losses and peak device memory go to
earlier lines; the last line is one JSON object naming the device.  The
script runs in one process and starts none.  It exits non-zero, printing
no result, where JAX finds no TPU or any phase fails.
"""

from __future__ import annotations

import argparse
import json
import math
import shutil
import statistics
import sys
import time
import traceback
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parent
ARCH = "distilgpt2-82m"
SEED = 0
SEQ_LEN = 1024
BATCH = 8
TRAIN_STEPS = 5
PROMPT_LEN = 512
GEN_TOKENS = 64
#: distilgpt2's stacked MLP up-projection, and its vocabulary-wide unembedding row
KERNEL_LEAVES = ((6, 768, 3072), (768, 50257))
FOUR_CHIP_STEPS = 3
SYNCED = ("allreduce", "hier", "hier_int8", "ps")
CONTROL = "local_sgd"
#: relative bound on each step's loss against the one-chip run.  On a TPU v5e
#: the synced strategies read at most 3.05e-6 and the unsynced control
#: 1.13e-4 at step 1, so the bound sits between the two.
LOSS_RTOL = 3e-5


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


def tpu_devices():
    """The TPU devices JAX sees; exits non-zero where there are none."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(
            f"chip_smoke: JAX found no TPU (platform {devs[0].platform!r}); "
            "nothing was run",
            file=sys.stderr,
        )
        sys.exit(2)
    return devs


def memory_report(devs) -> str:
    parts = []
    for d in devs:
        stats = d.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        parts.append(f"dev{d.id}={peak / 2**30:.3f}GiB" if peak else f"dev{d.id}=n/a")
    return "peak memory " + " ".join(parts)


# -- one chip ------------------------------------------------------------------


def reference_loss(cfg) -> float:
    """``loss_fn`` on the trainer's initial parameters and first batch."""
    import jax

    from repro.data import loader_for_model
    from repro.models import init_params, loss_fn

    params = init_params(jax.random.PRNGKey(SEED), cfg)
    first = loader_for_model(cfg, seq_len=SEQ_LEN, global_batch=BATCH, seed=SEED).next_batch()
    loss = jax.jit(lambda p, b: loss_fn(p, b, cfg)[0])(params, first)
    return float(loss)


def train_argv(strategy: str, steps: int, ckpt: Path) -> list:
    return [
        "--arch", ARCH, "--full-config", "--seq-len", str(SEQ_LEN),
        "--global-batch", str(BATCH), "--steps", str(steps), "--strategy", strategy,
        "--checkpoint-dir", str(ckpt), "--checkpoint-every", str(steps), "--seed", str(SEED),
    ]


def train_phase(work: Path, devs) -> None:
    import jax
    import numpy as np

    from repro.checkpoint import CheckpointStore
    from repro.configs import get_config
    from repro.launch import train

    steps = TRAIN_STEPS
    ckpt = work / "train_ckpt"
    t0 = time.perf_counter()
    result = train.main(train_argv("hier", steps, ckpt))
    wall = time.perf_counter() - t0
    losses = [row["loss"] for row in result["metrics"]]
    step_s = [row["step_s"] for row in result["metrics"]]
    log(f"[train] losses {losses}")
    log(
        f"[train] wall {wall:.2f}s | compile {result['compile']['seconds']:.2f}s "
        f"(cache {result['compile']['cache_hits']} hits / "
        f"{result['compile']['cache_misses']} misses) | first step {step_s[0]:.4f}s | "
        f"steady step median {statistics.median(step_s[1:]):.4f}s over {len(step_s) - 1}"
    )
    log(f"[train] {memory_report(devs)}")
    check(len(losses) == steps, f"trained {len(losses)} of {steps} steps")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss in {losses}")

    ref = reference_loss(get_config(ARCH))
    log(f"[train] step-0 loss {losses[0]:.6f} vs loss_fn {ref:.6f} (rel {rel(losses[0], ref):.2e})")
    check(rel(losses[0], ref) <= LOSS_RTOL, "step-0 loss disagrees with loss_fn")

    store = CheckpointStore(ckpt)
    check(store.latest_step() == steps, f"latest checkpoint {store.latest_step()} != {steps}")
    final = (result["params"], result["state"])
    restored, meta = store.restore(steps, final)
    same = jax.tree.map(
        lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b))), final, restored
    )
    check(all(jax.tree.leaves(same)), "restored checkpoint differs from the final state")
    log(f"[train] checkpoint step {steps} restored bit-exact ({meta})")


def serve_phase(devs) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.launch import serve
    from repro.launch.batches import synthetic_prompt_batch
    from repro.models import forward, init_params

    batch, prompt, gen = BATCH, PROMPT_LEN, GEN_TOKENS
    out = serve.main([
        "--arch", ARCH, "--full-config", "--batch", str(batch), "--prompt-len", str(prompt),
        "--gen", str(gen), "--seed", str(SEED),
    ])
    log(
        f"[serve] prefill {batch}x{prompt}: run {out['prefill_s']:.4f}s, compile "
        f"{out['prefill_compile_s']:.2f}s | decode {gen} steps: run {out['decode_s']:.4f}s "
        f"({out['decode_s'] / gen * 1e3:.3f} ms/step, {out['decode_tokens_per_s']:.1f} tok/s), "
        f"compile {out['decode_compile_s']:.2f}s | cache {out['compile_cache_hits']} hits / "
        f"{out['compile_cache_misses']} misses"
    )
    log(f"[serve] {memory_report(devs)}")
    cfg = get_config(ARCH)
    tokens = np.asarray(out["tokens"])
    check(tokens.shape == (batch, gen + 1), f"generated shape {tokens.shape}")
    check(bool(((tokens >= 0) & (tokens < cfg.vocab_size)).all()), "token outside vocabulary")
    last = np.asarray(out["last_logits"], np.float32)
    check(bool(np.isfinite(last).all()), "non-finite decode logits")

    # reference: one forward pass over prompt + generated tokens (no cache)
    key = jax.random.PRNGKey(SEED)
    params = init_params(key, cfg)
    seq = jnp.concatenate(
        [synthetic_prompt_batch(cfg, key, batch, prompt)["tokens"], tokens[:, :-1]], axis=1
    )
    logits = jax.jit(lambda p, t: forward(p, {"tokens": t}, cfg)[0][:, -1])(params, seq)
    ref = np.asarray(logits, np.float32)
    err = float(np.abs(last - ref).max() / np.abs(ref).max())
    greedy = float((ref.argmax(-1) == tokens[:, -1]).mean())
    log(f"[serve] last decode logits vs forward: max err {err:.2e} of max |logit|, "
        f"greedy agreement {greedy:.3f}")
    check(err <= 5e-2, f"decode logits disagree with forward ({err:.2e})")


def kernel_phase() -> None:
    for shape in KERNEL_LEAVES:
        kernel_leaf(shape)


def kernel_leaf(shape) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.wan_quant import (
        BLOCK, dequantize, quantize, wan_dequant_ref, wan_quant_ref,
    )

    x = jax.random.normal(jax.random.PRNGKey(SEED), shape, jnp.float32) * 0.02
    t0 = time.perf_counter()
    q_c = quantize.lower(x).compile()
    t_qc = time.perf_counter() - t0
    t0 = time.perf_counter()
    q, s = q_c(x)
    q.block_until_ready()
    t_q = time.perf_counter() - t0
    t0 = time.perf_counter()
    d_c = dequantize.lower(q, s, orig_shape=shape).compile()
    t_dc = time.perf_counter() - t0
    t0 = time.perf_counter()
    back = d_c(q, s)
    back.block_until_ready()
    t_d = time.perf_counter() - t0
    log(f"[kernels] wan_quant {shape}: compile {t_qc:.2f}s, run {t_q * 1e3:.3f}ms | "
        f"wan_dequant: compile {t_dc:.2f}s, run {t_d * 1e3:.3f}ms (first call, "
        "host clock)")

    rows = x.reshape(-1, shape[-1])
    q_r, s_r = wan_quant_ref(jnp.pad(rows, ((0, 0), (0, (-shape[-1]) % BLOCK))))
    dq = np.abs(np.asarray(q, np.int32) - np.asarray(q_r, np.int32))
    log(f"[kernels] {shape} quant vs ref: max |dq| {dq.max()}, differing {(dq != 0).mean():.2e}")
    check(dq.max() <= 1 and (dq != 0).mean() < 1e-3, "wan_quant disagrees with ref")
    check(np.allclose(np.asarray(s), np.asarray(s_r), rtol=1e-6, atol=0), "scales disagree")
    back_r = wan_dequant_ref(q, s)[:, : shape[-1]].reshape(shape)
    check(np.allclose(np.asarray(back), np.asarray(back_r), rtol=1e-6, atol=0),
          "wan_dequant disagrees with ref")
    err = float(jnp.abs(back - x).max())
    log(f"[kernels] {shape} round-trip max error {err:.3e}")


# -- four chips ----------------------------------------------------------------


def one_chip_losses(work: Path, cfg) -> list:
    """Losses of the same training run on the first device alone."""
    import jax

    from repro.launch.mesh import make_mesh
    from repro.runtime import GeoTrainer, TrainerConfig

    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    trainer = GeoTrainer(
        cfg, mesh,
        trainer_cfg=TrainerConfig(
            seq_len=SEQ_LEN, global_batch=BATCH, steps=FOUR_CHIP_STEPS,
            strategy="allreduce", checkpoint_every=FOUR_CHIP_STEPS, seed=SEED,
        ),
        checkpoint_dir=str(work / "one_chip"),
    )
    result = trainer.run()
    shutil.rmtree(work / "one_chip", ignore_errors=True)
    return [row["loss"] for row in result["metrics"]]


def four_chip_phase(work: Path, devs) -> None:
    import jax

    from repro.configs import get_config
    from repro.launch import train

    check(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    t0 = time.perf_counter()
    ref = one_chip_losses(work, get_config(ARCH))
    log(f"[4chip] one-chip reference losses {ref} ({time.perf_counter() - t0:.2f}s)")
    failures = []
    for strategy in SYNCED + (CONTROL,):
        ckpt = work / f"ckpt_{strategy}"
        t0 = time.perf_counter()
        result = train.main(train_argv(strategy, FOUR_CHIP_STEPS, ckpt) + ["--pods", "2"])
        wall = time.perf_counter() - t0
        losses = [row["loss"] for row in result["metrics"]]
        step_s = [row["step_s"] for row in result["metrics"]]
        params_devs = {d.id for leaf in jax.tree.leaves(result["params"])
                       for d in leaf.sharding.device_set}
        mesh_shape = dict(jax.tree.leaves(result["params"])[0].sharding.mesh.shape)
        log(f"[4chip] {strategy}: losses {losses}")
        log(
            f"[4chip] {strategy}: wall {wall:.2f}s | compile {result['compile']['seconds']:.2f}s "
            f"| steps {[round(x, 4) for x in step_s]} | params on {len(params_devs)} devices, "
            f"batch on {result['batch_devices']} devices, mesh {mesh_shape}"
        )
        log(f"[4chip] {strategy}: {memory_report(devs)}")
        errs = [rel(a, b) for a, b in zip(losses, ref)]
        expect = "within" if strategy in SYNCED else "step 0 within, later outside"
        log(f"[4chip] {strategy}: rel err vs one chip {[f'{e:.2e}' for e in errs]} "
            f"(expected {expect} {LOSS_RTOL:g})")
        try:
            check(len(losses) == FOUR_CHIP_STEPS, f"trained {len(losses)} steps")
            check(all(math.isfinite(x) for x in losses), "non-finite loss")
            check(len(params_devs) == 4 and result["batch_devices"] == 4,
                  "state or batch not spread over 4 devices")
            check(errs[0] <= LOSS_RTOL, "step-0 loss disagrees with one chip")
            if strategy in SYNCED:
                check(max(errs) <= LOSS_RTOL,
                      f"losses disagree with one chip beyond {LOSS_RTOL:g}")
            else:
                check(min(errs[1:]) > LOSS_RTOL,
                      f"the unsynced control matches one chip within {LOSS_RTOL:g}: "
                      "the bound cannot tell a missing cross-pod exchange")
        except SmokeFailure as e:
            failures.append(f"{strategy}: {e}")
        del result
        shutil.rmtree(ckpt, ignore_errors=True)
    check(not failures, "; ".join(failures))


# -- entry point ---------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2-pod four-chip training phase")
    args = ap.parse_args(argv)

    devs = tpu_devices()
    sys.path.insert(0, str(CHECKOUT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    log(f"device {devs[0].device_kind} x{len(devs)}; compile cache {enable_compile_cache()}")
    work = CHECKOUT / ".smoke_runs"
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir()
    if args.four_chips:
        phases = [("four_chips", lambda: four_chip_phase(work, devs))]
    else:
        phases = [
            ("train", lambda: train_phase(work, devs)),
            ("serve", lambda: serve_phase(devs)),
            ("kernels", kernel_phase),
        ]
    failed = []
    try:
        for name, run in phases:
            t0 = time.perf_counter()
            try:
                run()
            except Exception as e:  # noqa: BLE001 — report every phase, then fail
                failed.append(name)
                traceback.print_exc()
                log(f"[{name}] FAILED: {type(e).__name__}: {e}")
            log(f"[{name}] phase wall {time.perf_counter() - t0:.2f}s")
    finally:
        shutil.rmtree(work, ignore_errors=True)
    if failed:
        print(f"chip_smoke: phases failed: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
