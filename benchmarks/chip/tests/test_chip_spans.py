"""The reduction of the program's own annotations: idle gaps named by the
runtime loop's spans, and device time by named scope, on hand-made events
and on a trace recorded here on the CPU through the trainer."""

import sys
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import tiny_cells  # noqa: E402,F401  (puts the checkout and src on the path)

from benchmarks.chip import harness, spans  # noqa: E402
from benchmarks.chip import trace as tr  # noqa: E402

MS = 1e6  # nanoseconds per ms: the hand-made events below are in ms
WAITS = {"input_wait_ms.train": "repro.train.feed", "dispatch_wait_ms.train": "repro.train.dispatch",
         "fetch_wait_ms.train": "repro.train.fetch"}
SCOPED = ("attention_ms.train", "head_loss_ms.train", "optimizer_ms.train", "collective_exposed_ms.train4")


def _ms(events):
    return [(n, s * MS, e * MS) for n, s, e in events]


def _program():
    """Two runs of the step program, (0, 10) and (15, 25), and a third at
    (30, 40); an operation of another program at (26, 27) between them."""
    step_ops = [  # (scope path, start, end) in the first run; the second is 15 later
        ("jit(inner)/transpose(jvp())/while", 0, 6),
        ("jit(inner)/transpose(jvp())/while/body/closed_call/attention/dot_general", 1, 3),
        ("jit(inner)/transpose(jvp())/while/body/closed_call/attention_decode/add", 3, 4),
        ("jit(inner)/transpose(jvp())/while/body/dynamic_slice", 4, 5),
        ("jit(inner)/transpose(jvp(head))/dot_general", 6, 8),
        ("jit(inner)/jvp(loss)/reduce_max", 8, 9),
        ("jit(inner)/adamw/mul", 9, 10),
    ]
    paths = [(p, s + k, e + k) for k in (0, 15, 30) for p, s, e in step_ops]
    paths.insert(len(step_ops) * 2, ("jit(other)/attention/add", 26, 27))
    paths = _ms(paths)
    dev = tr.Device(
        "/device:TPU:0",
        ops=[("op", s, e) for _, s, e in paths],
        modules=_ms([("jit_inner(1)", 0, 10), ("jit_inner(1)", 15, 25), ("jit_other(2)", 26, 27),
                     ("jit_inner(1)", 30, 40)]),
    )
    host = _ms([
        ("repro.train.fetch", 5, 11), ("repro.train.bookkeep", 11, 12), ("repro.train.callback", 12, 13),
        ("repro.train.feed", 13, 14), ("repro.train.dispatch", 14, 16),
        ("repro.train.fetch", 20, 26.5), ("repro.train.bookkeep", 26.5, 27.5),
        ("repro.train.feed", 27.5, 28), ("repro.train.dispatch", 28, 31),
    ])
    return spans.Program(host=host, devices=[dev], scopes={dev.name: paths})


def test_idle_gaps_are_split_by_the_host_span_over_them():
    prog = _program()
    lo, hi = 0, 41 * MS
    # gaps (10, 15) and (25, 30), less the other program's (26, 27)
    assert spans.gap_idle(prog, "repro.train.fetch", lo, hi) == pytest.approx((1 + 1) / 2)
    assert spans.gap_idle(prog, "repro.train.bookkeep", lo, hi) == pytest.approx((1 + 0.5) / 2)
    assert spans.gap_idle(prog, "repro.train.callback", lo, hi) == pytest.approx(1 / 2)
    assert spans.gap_idle(prog, "repro.train.feed", lo, hi) == pytest.approx((1 + 0.5) / 2)
    assert spans.gap_idle(prog, "repro.train.dispatch", lo, hi) == pytest.approx((1 + 2) / 2)
    names = ("fetch", "bookkeep", "callback", "feed", "dispatch")
    split = sum(spans.gap_idle(prog, f"repro.train.{n}", lo, hi) for n in names)
    step_gap = sum(tr.idle_between_runs(spans.step_runs(prog.devices[0], lo, hi), prog.devices[0].busy()))
    assert split == pytest.approx(step_gap / 2 / MS)  # the spans cover every gap whole
    assert spans.gap_idle(prog, "repro.train.events", lo, hi) is None


@pytest.mark.parametrize("scopes,per_run", [
    (["attention"], 2),  # not attention_decode, nor the other program's op
    (["head", "loss"], 3),  # transpose(jvp(head)) is under head
    (["adamw"], 1),
    (["sync"], None),  # no operation carries it
])
def test_scope_self_time_per_step_run(scopes, per_run):
    got = spans.scope_time(_program(), scopes, 0, 41 * MS)
    assert got == (None if per_run is None else pytest.approx(per_run))


def _exchange():
    """Two runs of the step program, (0, 10) and (15, 25), with the cross-pod
    exchange in each: partly under compute, under ``wan_int8``, and nested in
    a loop whose own time is not compute; a ``sync`` op between the runs."""
    paths = _ms([
        ("jit(inner)/transpose(jvp())/attention/dot_general", 0, 4),
        ("jit(inner)/shard_map/sync/all-gather", 3, 6),  # 3..4 under compute
        ("jit(inner)/shard_map/wan_int8/convert", 6, 7),
        ("jit(inner)/adamw/mul", 7, 10),
        ("jit(inner)/sync/all-reduce", 11, 12),  # between the runs
        ("jit(inner)/while", 15, 22),
        ("jit(inner)/while/body/sync/all-reduce", 16, 18),  # inside the loop's event
        ("jit(inner)/while/body/ffn/dot_general", 18, 20),
        ("jit(inner)/adamw/mul", 22, 25),
    ])
    dev = tr.Device("/device:TPU:0", ops=[("op", s, e) for _, s, e in paths],
                    modules=_ms([("jit_inner(1)", 0, 10), ("jit_inner(1)", 15, 25)]))
    return spans.Program(host=[], devices=[dev], scopes={dev.name: paths})


def test_exposed_exchange_counts_only_time_no_other_operation_covers(monkeypatch):
    prog = _exchange()
    # run 1: 4..6 of the all-gather and 6..7 of wan_int8; run 2: 16..18
    assert spans.exposed_time(prog, ["sync", "wan_int8"], 0, 26 * MS) == pytest.approx((3 + 2) / 2)
    assert spans.exposed_time(prog, ["sync"], 0, 26 * MS) == pytest.approx((2 + 2) / 2)
    assert spans.exposed_time(_program(), ["sync", "wan_int8"], 0, 41 * MS) is None
    rec = types.SimpleNamespace(trace=object(), window_ns=(0, 26 * MS))
    monkeypatch.setattr(spans, "of", lambda rec: prog)
    assert harness.reader("collective_exposed_ms.train4")(rec) == pytest.approx(2.5)


def test_own_intervals_leave_out_nested_operations():
    ops = [("while", 0, 10), ("a", 1, 3), ("b", 2, 12), ("c", 13, 14)]
    assert spans.own_intervals(ops) == [("while", [(0, 1), (3, 10)]), ("a", [(1, 3)]), ("b", [(2, 12)]),
                                         ("c", [(13, 14)])]


def test_scope_names_peel_transformations():
    assert spans.scope_names("jit(inner)/transpose(jvp(head))/dot_general") == ["inner", "head", "dot_general"]
    assert spans.under("jit(f)/decode/while/body/attention/kv_cache/dynamic_update_slice", ["kv_cache"])
    assert not spans.under("jit(f)/attention_decode/add", ["attention"])


def _field(num: int, value) -> bytes:
    """One protobuf field: an int as a varint, bytes or str length-delimited."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(num << 3 | 2) + varint(len(value)) + value


def _xspace() -> bytes:
    def stat_meta(i, name):
        return _field(5, _field(1, i) + _field(2, _field(1, i) + _field(2, name)))

    def event_meta(i, name, *stats):
        return _field(4, _field(1, i) + _field(2, _field(1, i) + _field(2, name) + b"".join(stats)))

    def stat(meta, **value):
        (kind, v), = value.items()
        return _field(5, _field(1, meta) + _field({"str": 5, "ref": 7}[kind], v))

    device = (_field(1, 7) + _field(2, "/device:TPU:0")
              + stat_meta(3, "flops") + stat_meta(4, "tf_op") + stat_meta(9, "jit(f)/shared/add:")
              + event_meta(1, "%fusion.1 = f32[8] fusion()", stat(3, str="12"), stat(4, str="jit(f)/attention/dot:"))
              + event_meta(2, "%add.2 = f32[8] add()", stat(4, ref=9))
              + event_meta(3, "%copy-start.3 = f32[8] copy-start()", stat(3, str="0"))
              + event_meta(4, "%x.4 = f32[] x()", stat(4, str="jit(f)/head/x:"))
              + event_meta(5, "%x.4 = f32[] x()", stat(4, str="jit(f)/loss/x:")))
    host = _field(2, "/host:CPU") + stat_meta(4, "tf_op") + event_meta(1, "repro.train.step", stat(4, str="no"))
    return _field(1, device) + _field(1, host) + _field(4, "hostname")


def test_scope_paths_come_from_the_event_metadata():
    assert spans.scope_paths(_xspace()) == {"/device:TPU:0": {
        "%fusion.1 = f32[8] fusion()": "jit(f)/attention/dot",
        "%add.2 = f32[8] add()": "jit(f)/shared/add",  # a string kept once, by reference
        "%x.4 = f32[] x()": "",  # one name, two paths
    }}


def test_a_trace_recorded_through_the_trainer(tmp_path, monkeypatch):
    import jax

    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_host_mesh
    from repro.runtime import GeoTrainer, TrainerConfig

    tc = TrainerConfig(seq_len=16, global_batch=2, steps=8, checkpoint_every=100, log_every=100)
    trainer = GeoTrainer(get_smoke_config("distilgpt2-82m"), make_host_mesh(), trainer_cfg=tc,
                         checkpoint_dir=str(tmp_path / "ckpt"))

    def on_step(step, row):
        if step == 2:  # steps 0-2 compile and warm up
            jax.profiler.start_trace(str(tmp_path / "trace"))

    trainer.run(on_step=on_step)
    jax.profiler.stop_trace()

    monkeypatch.setattr(harness, "WORK", tmp_path)
    trace = tr.load(str(tmp_path / "trace"))
    rec = types.SimpleNamespace(trace=trace, window_ns=tr.window_of(trace, "bench.window"))
    waits = {name: harness.reader(name)(rec) for name in WAITS}
    assert all(isinstance(v, float) and v >= 0 for v in waits.values()), waits
    prog = spans.of(rec)
    dev = prog.devices[0]
    runs = spans.step_runs(dev, *rec.window_ns)
    assert len(runs) >= 4
    gap = sum(tr.idle_between_runs(runs, dev.busy())) / (len(runs) - 1) / MS
    assert sum(waits.values()) <= gap * (1 + 1e-9)
    assert {n for n, _, _ in prog.host} >= set(WAITS.values()) | {"repro.train.step"}
    # the CPU trace carries no scope path
    assert all(harness.reader(name)(rec) is None for name in SCOPED)


def test_the_new_metrics_read_nothing_untraced():
    rec = types.SimpleNamespace(trace=None, window_ns=None)
    for name in (*WAITS, *SCOPED):
        assert harness.reader(name)(rec) is None
