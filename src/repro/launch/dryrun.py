"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input-shape) cell, on BOTH production meshes
(16x16 single-pod and 2x16x16 multi-pod):

    with mesh:
        lowered  = jax.jit(step, in_shardings=..., out_shardings=...) \
                      .lower(**input_specs(arch, shape))
        compiled = lowered.compile()
        compiled.memory_analysis()   # proves the cell fits per-device HBM
        compiled.cost_analysis()     # FLOPs/bytes for the roofline

plus two UNROLLED cost probes (L = pattern, 2*pattern layers at full
width/shape) whose difference yields exact per-layer-group FLOPs/bytes/
collective-bytes — necessary because ``cost_analysis`` counts a
``lax.scan`` body once (measured; see DESIGN.md §6).

Artifacts land in artifacts/dryrun/<arch>__<shape>__<mesh>.json; the
roofline benchmark and EXPERIMENTS.md read from there.

The meshes are 512 fake CPU devices: ``main`` pins JAX to the CPU and
sets ``XLA_FLAGS`` before the first device query, so importing this
module changes nothing, and the per-cell worker processes never reach
for an accelerator the parent may hold.

Usage:
    python -m repro.launch.dryrun --arch yi-34b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh both [--skip-existing]
"""

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.distributed import (
    batch_shardings,
    cache_shardings,
    make_train_step,
    params_shardings,
)
from repro.distributed.act_sharding import activation_sharding
from repro.launch.hlo_stats import parse_collectives, scan_trip_counts
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import (
    SHAPES,
    decode_cache_specs,
    input_specs,
    params_specs,
    shape_supported,
)
from repro.models import decode_step, prefill
from repro.optim import AdamWConfig

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

#: environment of the dry-run process and its per-cell workers: CPU only,
#: with enough fake devices for the 2x16x16 production mesh
CPU_ENV = {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": "--xla_force_host_platform_device_count=512"}

# v5e-class chip constants (roofline; see EXPERIMENTS.md §Roofline)
PEAK_FLOPS_BF16 = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


def _mesh_for(name: str):
    return make_production_mesh(multi_pod=(name == "multi"))


def _lower_train(cfg, mesh, batch_specs):
    """Lower the full train step (the deliverable-(e) artifact)."""
    p_shapes = params_specs(cfg)
    step, sh = make_train_step(
        cfg, mesh,
        opt_cfg=AdamWConfig(),
        strategy="hier" if "pod" in mesh.axis_names else "allreduce",
        params_shapes=p_shapes,
        batch_shapes=batch_specs["batch"],
        donate=False,
    )
    from repro.distributed.steps import init_train_state

    state_shapes = jax.eval_shape(
        lambda p: init_train_state(
            p, AdamWConfig(), strategy="hier" if "pod" in mesh.axis_names else "allreduce"
        ),
        p_shapes,
    )
    return step.lower(p_shapes, state_shapes, batch_specs["batch"])


def _lower_prefill(cfg, mesh, batch_specs):
    p_shapes = params_specs(cfg)
    p_shard = params_shardings(p_shapes, mesh)
    b_shard = batch_shardings(batch_specs["batch"], mesh)
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    seq_axes = "model" if "model" in mesh.axis_names else None

    def fn(params, batch):
        with activation_sharding(batch_axes, seq_axes):
            return prefill(params, batch, cfg)

    cache_shapes = jax.eval_shape(fn, p_shapes, batch_specs["batch"])[1]
    c_shard = cache_shardings(cache_shapes, mesh)
    jitted = jax.jit(fn, in_shardings=(p_shard, b_shard), out_shardings=(None, c_shard))
    return jitted.lower(p_shapes, batch_specs["batch"])


def _lower_decode(cfg, mesh, shape_name: str):
    p_shapes = params_specs(cfg)
    cache_shapes = decode_cache_specs(cfg, shape_name)
    tok = input_specs(cfg, shape_name)["tokens_t"]
    p_shard = params_shardings(p_shapes, mesh)
    c_shard = cache_shardings(cache_shapes, mesh)
    b_shard = batch_shardings({"t": tok}, mesh)["t"]
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def fn(params, tokens_t, cache, position):
        with activation_sharding(batch_axes):
            return decode_step(params, tokens_t, cache, cfg, position)

    jitted = jax.jit(
        fn,
        in_shardings=(p_shard, b_shard, c_shard, None),
        out_shardings=(None, c_shard),
    )
    return jitted.lower(
        p_shapes, tok, cache_shapes, jax.ShapeDtypeStruct((), jnp.int32)
    )


def lower_cell(cfg, mesh, shape_name: str):
    kind = SHAPES[shape_name].kind
    if kind == "train":
        return _lower_train(cfg, mesh, input_specs(cfg, shape_name))
    if kind == "prefill":
        return _lower_prefill(cfg, mesh, input_specs(cfg, shape_name))
    return _lower_decode(cfg, mesh, shape_name)


def analyse(lowered, mesh) -> dict:
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    pod_size = 1
    for name, size in mesh.shape.items():
        if name != "pod":
            pod_size *= size
    colls = parse_collectives(hlo, pod_size=pod_size if "pod" in mesh.axis_names else 0)
    return {
        "flops_per_device": float(cost.get("flops", 0.0)),
        "bytes_per_device": float(cost.get("bytes accessed", 0.0)),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_estimate_bytes": mem.argument_size_in_bytes
            + mem.temp_size_in_bytes
            + mem.output_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "collectives": {
            "by_kind": colls.bytes_by_kind,
            "total_bytes": colls.total_bytes,
            "cross_pod_bytes": colls.cross_pod_bytes,
            "unclassified_bytes": colls.unclassified_bytes,
            "count": colls.count,
        },
        "scan_trip_counts": scan_trip_counts(hlo),
        "hlo_size_chars": len(hlo),
    }


def probe_costs(cfg, mesh, shape_name: str) -> dict:
    """Unrolled L=|pattern| and L=2|pattern| probes -> per-group costs."""
    plen = len(cfg.pattern)
    probes = {}
    for mult in (1, 2):
        pcfg = dataclasses.replace(
            cfg, num_layers=mult * plen, scan_layers=False, remat="none"
        )
        lowered = lower_cell(pcfg, mesh, shape_name)
        probes[mult] = analyse(lowered, mesh)
    g_flops = probes[2]["flops_per_device"] - probes[1]["flops_per_device"]
    g_bytes = probes[2]["bytes_per_device"] - probes[1]["bytes_per_device"]
    g_coll = (
        probes[2]["collectives"]["total_bytes"]
        - probes[1]["collectives"]["total_bytes"]
    )
    n_groups_total = cfg.num_layers / plen  # fractional remainder ok
    base_flops = probes[1]["flops_per_device"] - g_flops
    base_bytes = probes[1]["bytes_per_device"] - g_bytes
    base_coll = probes[1]["collectives"]["total_bytes"] - g_coll
    return {
        "per_group": {"flops": g_flops, "bytes": g_bytes, "collective_bytes": g_coll},
        "base": {"flops": base_flops, "bytes": base_bytes, "collective_bytes": base_coll},
        "estimated_total": {
            "flops": base_flops + g_flops * n_groups_total,
            "bytes": base_bytes + g_bytes * n_groups_total,
            "collective_bytes": base_coll + g_coll * n_groups_total,
        },
        "probe1": probes[1],
        "probe2": probes[2],
    }


def attn_scan_correction(cfg, shape_name: str, chips: int) -> dict:
    """FLOP/byte correction for chunked (scanned) attention.

    ``cost_analysis`` counts the q-block scan body once per layer, i.e.
    1/n_blocks of the true attention work.  The missing part is exact
    arithmetic: per block, the two attention matmuls cost
    ``4 * B * block * kv_span * H * hd`` forward FLOPs (masked elements
    included — the dense-block HLO really computes them), and the block
    re-reads ``kv_span`` keys+values from HBM.  Train probes run with
    remat="none", so the backward multiplier is 3x (fwd + 2 bwd matmuls).
    Returns per-device corrections to ADD to the probe-estimated totals.
    """
    from repro.models.config import ATTN, LOCAL

    spec = SHAPES[shape_name]
    if spec.kind == "decode":
        return {"flops": 0.0, "bytes": 0.0, "n_blocks": 1}
    s, b = spec.seq_len, spec.global_batch
    block = cfg.attn_block
    chunked = cfg.attn_impl in ("auto", "chunked") and s >= 2 * block and s % block == 0
    if not chunked:
        return {"flops": 0.0, "bytes": 0.0, "n_blocks": 1}
    nb = s // block
    mult = 3.0 if spec.kind == "train" else 1.0
    h, hd, kvh = cfg.num_heads, cfg.head_dim, cfg.num_kv_heads
    kinds = list(cfg.pattern) * cfg.num_groups + list(cfg.remainder)
    miss_flops = 0.0
    miss_bytes = 0.0
    for kind in kinds:
        if kind == ATTN:
            window = cfg.window
        elif kind == LOCAL:
            window = cfg.local_window
        else:
            continue
        kv_span = s if window is None else min(window + block, s)
        per_block_flops = 4.0 * b * block * kv_span * h * hd
        per_block_bytes = 2.0 * b * kv_span * kvh * hd * 2  # k+v reads, bf16
        miss_flops += (nb - 1) * per_block_flops * mult
        miss_bytes += (nb - 1) * per_block_bytes * mult
    return {
        "flops": miss_flops / chips,
        "bytes": miss_bytes / chips,
        "n_blocks": nb,
    }


def model_flops(cfg, shape_name: str) -> float:
    """MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE); decode: D = batch."""
    spec = SHAPES[shape_name]
    n = cfg.active_param_count()
    if spec.kind == "train":
        tokens = spec.seq_len * spec.global_batch
        return 6.0 * n * tokens
    if spec.kind == "prefill":
        tokens = spec.seq_len * spec.global_batch
        return 2.0 * n * tokens  # forward only
    return 2.0 * n * spec.global_batch  # one token per sequence


def run_cell(arch: str, shape_name: str, mesh_name: str, *, probes: bool, out_dir: Path) -> dict:
    cfg = get_config(arch)
    ok, why = shape_supported(cfg, shape_name)
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "timestamp": time.strftime("%Y-%m-%d %H:%M:%S"),
    }
    if not ok:
        record["status"] = "skipped"
        record["reason"] = why
        return record
    mesh = _mesh_for(mesh_name)
    chips = 1
    for s in mesh.shape.values():
        chips *= s
    t0 = time.time()
    with mesh:
        lowered = lower_cell(cfg, mesh, shape_name)
        t_lower = time.time() - t0
        t0 = time.time()
        record["main"] = analyse(lowered, mesh)
        t_compile = time.time() - t0
        if probes:
            t0 = time.time()
            record["probes"] = probe_costs(cfg, mesh, shape_name)
            record["probes"]["seconds"] = time.time() - t0
    record["status"] = "ok"
    record["chips"] = chips
    record["lower_seconds"] = t_lower
    record["compile_seconds"] = t_compile
    record["model_flops_total"] = model_flops(cfg, shape_name)
    # roofline terms (single-pod only, per instructions)
    if mesh_name == "single" and "probes" in record:
        est = record["probes"]["estimated_total"]
        corr = attn_scan_correction(cfg, shape_name, chips)
        record["attn_scan_correction"] = corr
        flops = est["flops"] + corr["flops"]
        nbytes = est["bytes"] + corr["bytes"]
        record["roofline"] = {
            "compute_s": flops / PEAK_FLOPS_BF16,
            "memory_s": nbytes / HBM_BW,
            "collective_s": est["collective_bytes"] / ICI_BW,
            "model_flops_ratio": record["model_flops_total"] / chips / max(flops, 1.0),
        }
        terms = {k: record["roofline"][f"{k}_s"] for k in ("compute", "memory", "collective")}
        record["roofline"]["bottleneck"] = max(terms, key=terms.get)
    return record


def _run_one(arch: str, shape_name: str, mesh_name: str, probes: bool, out_dir: Path) -> dict:
    path = out_dir / f"{arch}__{shape_name}__{mesh_name}.json"
    t0 = time.time()
    try:
        rec = run_cell(arch, shape_name, mesh_name, probes=probes, out_dir=out_dir)
    except Exception as e:  # noqa: BLE001 — record and continue
        rec = {
            "arch": arch, "shape": shape_name, "mesh": mesh_name,
            "status": "error", "error": str(e)[:2000],
            "traceback": traceback.format_exc()[-4000:],
        }
    rec["wall_seconds"] = time.time() - t0
    path.write_text(json.dumps(rec, indent=2))
    return rec


def _print_cell(rec: dict, wall: float) -> None:
    status = rec.get("status", "error")
    extra = ""
    if status == "ok":
        mem = rec["main"]["memory"]["peak_estimate_bytes"] / 2**30
        extra = f"peak={mem:.2f}GiB colls={rec['main']['collectives']['count']}"
        if "roofline" in rec:
            r = rec["roofline"]
            extra += (
                f" compute={r['compute_s']*1e3:.1f}ms mem={r['memory_s']*1e3:.1f}ms"
                f" coll={r['collective_s']*1e3:.1f}ms bottleneck={r['bottleneck']}"
            )
    print(
        f"[{status}] {rec['arch']} {rec['shape']} {rec['mesh']} ({wall:.0f}s) {extra}",
        flush=True,
    )


def main() -> None:
    os.environ.update(CPU_ENV)  # read when the CPU backend is first created
    jax.config.update("jax_platforms", "cpu")
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list(ASSIGNED_ARCHS), default=None)
    ap.add_argument("--shape", choices=list(SHAPES), default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true", help="run every cell")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default=str(ARTIFACTS))
    ap.add_argument(
        "--in-process", action="store_true",
        help="run cells in this process (default: one subprocess per cell, "
        "so an XLA C++ CHECK abort cannot kill the whole sweep)",
    )
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    archs = list(ASSIGNED_ARCHS) if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    single_cell = len(archs) == 1 and len(shapes) == 1 and len(meshes) == 1

    failures = []
    for arch in archs:
        for shape_name in shapes:
            for mesh_name in meshes:
                path = out_dir / f"{arch}__{shape_name}__{mesh_name}.json"
                if args.skip_existing and path.exists():
                    try:
                        if json.loads(path.read_text()).get("status") in ("ok", "skipped"):
                            print(f"[skip] {path.name}")
                            continue
                    except Exception:  # noqa: BLE001
                        pass
                probes = not args.no_probes and mesh_name == "single"
                t0 = time.time()
                if single_cell or args.in_process:
                    rec = _run_one(arch, shape_name, mesh_name, probes, out_dir)
                else:
                    # isolate each cell: XLA partitioner CHECK failures abort
                    # the process; a subprocess confines the blast radius.
                    cmd = [
                        sys.executable, "-m", "repro.launch.dryrun",
                        "--arch", arch, "--shape", shape_name, "--mesh", mesh_name,
                        "--out", str(out_dir),
                    ]
                    if args.no_probes:
                        cmd.append("--no-probes")
                    proc = subprocess.run(
                        cmd, capture_output=True, text=True,
                        env={**os.environ, **CPU_ENV},
                    )
                    if path.exists():
                        rec = json.loads(path.read_text())
                    else:
                        rec = {
                            "arch": arch, "shape": shape_name, "mesh": mesh_name,
                            "status": "error",
                            "error": f"worker died rc={proc.returncode}",
                            "stderr_tail": proc.stderr[-3000:],
                        }
                        path.write_text(json.dumps(rec, indent=2))
                if rec.get("status") == "error":
                    failures.append(path.name)
                _print_cell(rec, time.time() - t0)
    if failures:
        print(f"\n{len(failures)} FAILURES: {failures}")
        raise SystemExit(1)
    print("\nall requested dry-run cells passed")


if __name__ == "__main__":
    main()
