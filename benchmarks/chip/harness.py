"""One run of one cell: find its files by name, drive it, print its line.

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration and a
traffic mix.  The harness finds

* the configuration at the ``file`` that ``BENCHMARK.json`` gives it;
* the traffic mix at ``traffic/<traffic>.json``; its ``kind`` picks the
  module that runs it (``drive_train`` or ``drive_serve``);
* the limits of the output comparison at ``limits/<cell>.json``;
* each per-layer metric's reader at ``metrics/<metric>.py``, a module with
  ``read(rec) -> float | None``;
* the plain reference's architecture at ``references/<config>.py``, a
  module with ``forward`` (``reference.lookup``), or the dense decoder of
  ``dense.py`` where the configuration brings none.

So a later change adds a cell, a traffic mix, a metric or an architecture
by adding files and entries, never by editing these.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import shutil
import sys
import time
import types
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
WORK = CHECKOUT / ".bench_work"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def cell_spec(name: str, bench_path: Path = CHECKOUT / "BENCHMARK.json") -> Dict:
    """Everything the benchmark declares for cell ``name``."""
    bench = load_json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def applies(metric: Dict) -> bool:
        return name in metric.get("workloads", [name])

    return {
        "cell": cell,
        "config": load_json(CHECKOUT / config["file"]),
        "traffic": load_json(HERE / "traffic" / f"{cell['traffic']}.json"),
        "limits": load_json(HERE / "limits" / f"{name}.json"),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def reader(metric: str):
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location("bench_metric_" + metric.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def chips(n: int):
    """The first ``n`` TPU devices; raises :class:`NoChip` where there are fewer."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def enable_cache() -> str:
    """The program's persistent compile cache, for every program of the cell."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return where


def memory_peak(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devs)


def fresh_dir(name: str) -> Path:
    path = WORK / name
    shutil.rmtree(path, ignore_errors=True)
    path.mkdir(parents=True)
    return path


class Tracer:
    """Profiler trace of the measured window (``--trace 1`` only)."""

    def __init__(self, on: bool):
        self.on = on
        self.dir: Optional[Path] = fresh_dir("trace") if on else None
        self._span = None

    def start(self) -> None:
        if self.on:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()

    def stop(self) -> None:
        if self.on and self._span is not None:
            import jax

            self._span.__exit__(None, None, None)
            self._span = None
            jax.profiler.stop_trace()


def per_layer(spec: Dict, rec) -> Dict[str, Dict]:
    out = {}
    for m in spec["per_layer"]:
        value = reader(m["name"])(rec)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def breakdown(rec) -> Optional[Dict[str, List]]:
    if rec.trace is None:
        return None
    from . import trace as tr

    lo, hi = rec.window_ns
    return {"device_ops": tr.top_ops(rec.trace, lo, hi), "idle_gaps": tr.idle_gaps(rec.trace, lo, hi)}


def main(argv=None, t_process: Optional[float] = None) -> int:
    t_process = time.perf_counter() if t_process is None else t_process
    ap = argparse.ArgumentParser(description="Run one cell of the chip benchmark once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = cell_spec(args.workload)
    import jax  # noqa: F401  (imported here so that its time is marked apart from the device's start-up)

    marks = [("import", time.perf_counter())]
    try:
        devs = chips(spec["cell"]["chips"])
    except NoChip as e:
        log(f"benchmark: {e}; nothing was run")
        return 2
    marks.append(("devices", time.perf_counter()))
    cache = enable_cache()
    log(f"device {devs[0].device_kind} x{len(devs)}; compile cache {cache}")

    print(json.dumps(execute(spec, devs, args.seed, args.seconds, bool(args.trace), t_process, marks)), flush=True)
    return 0


def execute(spec: Dict, devs, seed: int, seconds: float, trace: bool, t_process: float,
            marks: Optional[List] = None) -> Dict:
    """Drive the cell once and reduce what it measured to its result line.

    ``marks``: (phase, host clock) pairs of set-up so far; the driver adds its
    own, and they are printed as seconds from the process's start.
    """
    marks = list(marks or [])
    kind = spec["traffic"]["kind"]
    if kind == "train":
        from .drive_train import run
    elif kind == "serve":
        from .drive_serve import run
    else:
        raise SystemExit(f"unknown traffic kind {kind!r}")
    tracer = Tracer(bool(trace))
    out = run(spec, devs, seed=seed, seconds=seconds, tracer=tracer, t_process=t_process,
              mark=lambda phase: marks.append((phase, time.perf_counter())))

    from . import checks, counts
    from . import trace as tr

    rec = types.SimpleNamespace(
        model=spec["config"]["model"], traffic=spec["traffic"], chips=len(devs),
        peak=counts.peaks(devs[0].device_kind), window=out["window"], trace=None, window_ns=None,
    )
    device = {
        "platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
        "memory_peak_bytes": out["memory_peak_bytes"],
    }
    if trace:
        rec.trace = tr.load(str(tracer.dir))
        rec.window_ns = tr.window_of(rec.trace, "bench.window")
        lo, hi = rec.window_ns
        device["busy_s"] = tr.busy_seconds(rec.trace, lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        metrics = per_layer(spec, rec)
    else:
        names = {m["name"]: m["unit"] for m in spec["end_to_end"]}
        metrics = {k: {"value": v, "unit": names[k]} for k, v in out["end_to_end"].items() if k in names}
    correct, shown = checks.judge(out["numbers"], spec["limits"])
    log("info setup_marks " + ", ".join(f"{p} {t - t_process:.2f}s" for p, t in marks))
    for k, v in out.get("info", {}).items():
        log(f"info {k} {v}")
    for k, v in shown.items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    result = {
        "correct": correct, "attempted": out["attempted"], "failed": out["failed"],
        "metrics": metrics, "device": device,
    }
    if trace:
        result["breakdown"] = breakdown(rec)
    result["checks"] = shown
    return result
