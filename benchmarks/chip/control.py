"""Readings that set the limits of the output comparison, at a cell's own size.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3

The benchmark's own runs do not run this.  One process on one chip, one
line of JSON per seed; the reference is the configuration's own
(``reference.lookup``).  Training cells: the numbers of
``checks.train_numbers`` for

* ``control``: the reference put in the program's place and computed with
  float8 (e4m3, per-tensor scale) matmul operands, the step below the
  configuration's bfloat16;
* ``half_batch``: the reference with half of every batch left out and the
  mean taken over the rest;
* ``no_exchange`` (cells with pods): the reference with each pod keeping
  its own gradient, as a step whose cross-pod exchange is left out; pod
  0's readings are compared.

A step that returns its state unchanged reads ``update_gap`` = 1 by
construction and needs no run.

Serving cells: a short closed loop of the cell's batches, then over a
sample of the served requests, ``token_gap`` for

* ``program``: the served tokens;
* ``control``: at each position of the same prompts and tokens, the token
  the float8 reference puts first;
* ``token_altered``: the served tokens with one replaced by its successor;
* ``cache_unchanged``: the program with ``decode_step`` returning the
  cache it was given.
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if sys.path and Path(sys.path[0] or ".").resolve() == Path(__file__).resolve().parent:
    sys.path.pop(0)
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def train_readings(spec, seed: int):
    import numpy as np

    from benchmarks.chip import inputs, reference
    from benchmarks.chip.checks import train_numbers
    from benchmarks.chip.drive_train import model_config, weights_fn

    t, m = spec["traffic"], spec["config"]["model"]
    ref_of = reference.lookup(spec["cell"]["config"])
    cfg = model_config(spec["config"])
    pods, checked = t.get("pods", 1), t["checked_steps"]
    make = weights_fn(cfg)
    batches = inputs.train_pool(seed, cfg.vocab_size, t["pool_batches"], t["global_batch"], t["seq_len"])[:checked]

    def follow(batches, precision="f32", pods_run=pods, strategy=t["strategy"]):
        losses, grad, params = ref_of.train_steps(
            make(seed), batches, m, t["adamw"], strategy=strategy, pods=pods_run,
            precision=precision, rows_per_block=t["ref_rows_per_block"])
        return {"losses": losses, "grad": reference.to_host(grad),
                "change": reference.to_host(reference.change_norms(params, make(seed)))}

    ref = follow(batches)
    out = {"control": train_numbers(follow(batches, precision="fp8"), ref)}
    rows = t["global_batch"] // pods
    keep = np.concatenate([np.arange(p * rows, p * rows + rows // 2) for p in range(pods)])
    half = [{k: v[keep] for k, v in b.items()} for b in batches]
    out["half_batch"] = train_numbers(follow(half), ref)
    if pods > 1:
        # pod 0 alone: its own rows, its own (compressed) gradient, no exchange
        pod0 = [{k: v[:rows] for k, v in b.items()} for b in batches]
        if t["strategy"] == "hier_int8":
            readings = follow_int8_alone(ref_of, make(seed), pod0, m, t)
        else:
            readings = follow(pod0, pods_run=1)
        out["no_exchange"] = train_numbers(readings, ref)
    return out


def follow_int8_alone(ref_of, params, batches, m, t):
    """One pod that compresses its gradient with error feedback and keeps it
    (``ref_of``: the configuration's reference)."""
    import jax

    from benchmarks.chip import reference

    zeros = jax.jit(lambda t: jax.tree.map(jax.numpy.zeros_like, t))
    mom, vel, ef = zeros(params), zeros(params), zeros(params)
    params0 = jax.tree.map(lambda a: a.copy(), params)
    losses, first = [], None
    for k, b in enumerate(batches, start=1):
        loss, g = ref_of.loss_and_grad(params, b["tokens"], b["labels"], m, "f32", t["ref_rows_per_block"])
        losses.append(loss)
        boosted = reference.tree_add(g, ef)
        sent = reference.round_trip_tree(boosted)
        ef = reference.tree_sub(boosted, sent)
        params, mom, vel, clipped = reference.adamw(t["adamw"], sent, mom, vel, params, k)
        first = reference.leaf_norms(clipped) if first is None else first
    return {"losses": losses, "grad": reference.to_host(first),
            "change": reference.to_host(reference.change_norms(params, params0))}


def serve_readings(spec, seed: int, seconds: float):
    import jax

    from benchmarks.chip import inputs, reference
    from benchmarks.chip.drive_serve import Server, closed_loop, sample
    from benchmarks.chip.drive_train import model_config, weights_fn

    t, m = spec["traffic"], spec["config"]["model"]
    ref_of = reference.lookup(spec["cell"]["config"])
    cfg = model_config(spec["config"])
    B, P, rows = t["batch"], t["prompt_len"], t["ref_rows_per_block"]
    params = weights_fn(cfg)(seed)
    server = Server(cfg, t, params)
    server.batch(inputs.serve_batch(seed, "warmup", 0, B, P, cfg.vocab_size))
    loop = closed_loop(server, lambda b: inputs.serve_batch(seed, "window", b, B, P, cfg.vocab_size), seconds)
    prompts, served = sample(seed, loop, t["sample_requests"])
    gaps, ctrl = ref_of.served_gaps(params, prompts, served, m, precision="fp8", rows_per_block=rows)
    altered = served.copy()
    altered[0, t["gen_tokens"] // 2] = (altered[0, t["gen_tokens"] // 2] + 1) % cfg.vocab_size
    alt, _ = ref_of.served_gaps(params, prompts, altered, m, rows_per_block=rows)

    # the program with ``decode_step`` returning the cache it was given, on the
    # same batch as the sampled requests' first
    stuck = Server(cfg, t, params)
    program_decode = stuck.decode
    stuck.decode = jax.jit(lambda p, tok, cache, pos: (program_decode(p, tok, cache, pos)[0], cache))
    frozen = stuck.batch(loop["prompts"][:B])[0][: t["sample_requests"]]
    cache_gaps, _ = ref_of.served_gaps(params, loop["prompts"][: len(frozen)], frozen, m, rows_per_block=rows)
    return {"program": float(gaps.max()), "control": float(ctrl.max()),
            "token_altered": float(alt.max()), "cache_unchanged": float(cache_gaps.max()),
            "batches": len(loop["ttft"])}


def main() -> int:
    import argparse
    import json

    from benchmarks.chip import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0, help="serving: length of the closed loop")
    args = ap.parse_args()
    spec = harness.cell_spec(args.workload)
    try:
        harness.chips(1)
    except harness.NoChip as e:
        harness.log(f"control: {e}")
        return 2
    harness.enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        if spec["traffic"]["kind"] == "train":
            readings = train_readings(spec, seed)
        else:
            readings = serve_readings(spec, seed, args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed, "readings": readings}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
