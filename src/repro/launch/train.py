"""Training launcher: ``python -m repro.launch.train --arch <id> ...``.

Runs the GeoTrainer end to end on the selected architecture (full or
smoke-scale) and WAN sync strategy, over a mesh of whatever devices the
process sees: one chip, a four-chip host (``--pods 2`` gives
``(pod=2, data=2, model=1)``), or fake CPU devices under
``--xla_force_host_platform_device_count``.  Compiling the 256/512-chip
production meshes without the chips is ``repro.launch.dryrun``'s job.
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="distilgpt2-82m")
    ap.add_argument("--shape", default=None, help="named shape (train_4k) or custom")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--strategy", default="hier",
                    choices=["allreduce", "ps", "hier", "hier_int8", "local_sgd"])
    ap.add_argument("--num-channels", type=int, default=4)
    ap.add_argument("--full-config", action="store_true",
                    help="use the full (paper-scale) config instead of smoke")
    ap.add_argument("--pods", type=int, default=1, help="pod axis of the host mesh")
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--checkpoint-every", type=int, default=None)
    ap.add_argument("--inject-failure-at", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out-json", default=None)
    args = ap.parse_args(argv)

    import jax

    from repro.configs import get_config, get_smoke_config
    from repro.core.geo import GeoFabric
    from repro.launch.compile_cache import CompileStats, enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.launch.shapes import SHAPES
    from repro.runtime import GeoTrainer, TrainerConfig

    cache_dir = enable_compile_cache()
    cfg = get_config(args.arch) if args.full_config else get_smoke_config(args.arch)
    if args.shape is not None:
        spec = SHAPES[args.shape]
        args.seq_len, args.global_batch = spec.seq_len, spec.global_batch

    n = len(jax.devices())
    mesh = make_host_mesh(pods=args.pods, model=1) if n > 1 else make_host_mesh()

    npods = mesh.shape.get("pod", 1)
    geo = GeoFabric(num_pods=max(npods, 2), workers_per_pod=2, seed=args.seed)

    trainer = GeoTrainer(
        cfg, mesh,
        trainer_cfg=TrainerConfig(
            seq_len=args.seq_len,
            global_batch=args.global_batch,
            steps=args.steps,
            strategy=args.strategy,
            num_channels=args.num_channels,
            checkpoint_every=args.checkpoint_every,
            seed=args.seed,
        ),
        checkpoint_dir=args.checkpoint_dir,
        geo=geo,
    )
    with CompileStats() as compiles:
        result = trainer.run(inject_failure_at=args.inject_failure_at)
    if result["final_loss"] is None:
        raise SystemExit(
            f"nothing to do: checkpoint at {args.checkpoint_dir} already "
            f"covers {args.steps} steps (use --steps N or a fresh dir)"
        )
    print(
        f"\nfinal loss {result['final_loss']:.4f} | "
        f"sync efficiency {result['sync_efficiency']:.2f} | "
        f"last checkpoint step {result['last_checkpoint']}"
    )
    print(f"{compiles.summary()} ({cache_dir})")
    for drill in result["recovery_drills"]:
        p = drill["plan"]
        print(
            f"recovery drill @step {drill['step']}: dead={drill['dead']} "
            f"downtime={p['detection_s'] + p['restore_s'] + p['remesh_s']:.2f}s "
            f"lost_steps={p['lost_steps']}"
        )
    if args.out_json:
        Path(args.out_json).write_text(json.dumps(result["metrics"], indent=1))
    result["compile"] = {
        "seconds": compiles.compile_s,
        "cache_hits": compiles.hits,
        "cache_misses": compiles.misses,
        "cache_dir": cache_dir,
    }
    return result


if __name__ == "__main__":
    main()
