"""CPU rehearsal: the benchmark's files hang together, and a training and a
serving cell run their window through the harness at smoke size."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from tiny_cells import ROOT, SERVE, TRAIN, cpu_as_chip, tiny_spec  # noqa: E402

from benchmarks.chip import harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = {w["name"]: w for w in BENCH["workloads"]}


def reports(metric, cell):
    return cell in metric.get("workloads", [cell])


def test_every_cell_finds_its_files():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for name, cell in CELLS.items():
        spec = harness.cell_spec(name)
        assert (ROOT / configs[cell["config"]]["file"]).is_file()
        assert spec["traffic"]["kind"] in ("train", "serve")
        assert set(spec["limits"]) == ({"token_gap"} if spec["traffic"]["kind"] == "serve"
                                       else {"loss_gap", "grad_gap", "update_gap"})
        assert cell["chips"] in (1, 4)
        e2e = [m["name"] for m in BENCH["end_to_end"] if reports(m, name)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(reports(m, name) for m in BENCH["per_layer"])
    for metric in BENCH["per_layer"]:
        assert callable(harness.reader(metric["name"]))


def test_metric_cell_lists_match_the_cells_that_report_them():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for metric in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(metric.get("workloads", CELLS)) <= set(CELLS)
    for metric in BENCH["per_layer"]:
        moved = e2e[metric["moves"]]
        assert all(reports(moved, cell) for cell in metric["workloads"])
        kind = {harness.cell_spec(c)["traffic"]["kind"] for c in metric["workloads"]}
        assert len(kind) == 1


def test_no_chip_no_result(capsys):
    assert harness.main(["--workload", TRAIN, "--seed", "1", "--seconds", "1", "--trace", "0"]) == 2
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("cell,trace", [(TRAIN, 0), (SERVE, 1)])
def test_cell_runs_its_window_at_smoke_size(cell, trace, monkeypatch, capsys):
    cpu_as_chip(monkeypatch)
    spec = tiny_spec(cell)
    monkeypatch.setattr(harness, "cell_spec", lambda name: spec)
    argv = ["--workload", cell, "--seed", str(2**31 + 7), "--seconds", "0.3", "--trace", str(trace)]
    assert harness.main(argv) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["count"] == 1
    names = {m["name"] for m in BENCH["per_layer" if trace else "end_to_end"] if reports(m, cell)}
    if trace:
        assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert set(line["metrics"]) <= names and line["metrics"]
    else:
        assert set(line["metrics"]) == names


def test_benchmark_json_keeps_to_its_format():
    import re

    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"} and name.match(c["name"])
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"]
        assert c["file"].startswith(BENCH["paths"][0] + "/")
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"]) and 1 <= len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(BENCH["workloads"]) // 2)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert 1 <= len(m["layer"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"]) and m["better"] in ("lower", "higher")
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["layer"].split(" (")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
