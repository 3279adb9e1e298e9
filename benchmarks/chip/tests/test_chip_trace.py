"""The trace reduction: interval arithmetic on hand-made intervals, and a
small trace recorded here on the CPU."""

import sys
from pathlib import Path

import pytest

sys.path[:0] = [str(Path(__file__).resolve().parents[3]), str(Path(__file__).resolve().parents[3] / "src")]

from benchmarks.chip import trace as tr  # noqa: E402


def test_merge_and_length():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3), (5, 8)]
    assert tr.length([(0, 2), (1, 3), (5, 8)]) == 6


def test_busy_union_and_idle_share():
    busy = [(0, 2), (1, 3), (6, 8), (9, 12)]
    # window [1, 10]: busy 1..3, 6..8, 9..10 = 2 + 2 + 1 = 5 of 9
    assert tr.length(tr.clip(busy, 1, 10)) == 5
    assert tr.idle_share(busy, 1, 10) == pytest.approx(4 / 9)
    assert tr.idle_intervals(busy, 1, 10) == [(3, 6), (8, 9)]


def test_overlap_and_uncovered():
    a = [(0, 10), (20, 30)]
    b = [(5, 25), (28, 40)]
    assert tr.overlap(a, b) == 5 + 5 + 2
    assert tr.uncovered(a, b) == 20 - 12


def test_idle_between_program_runs():
    runs = [(0, 10), (12, 20), (20, 25), (30, 31)]
    # an operation inside a gap is not idle time
    busy = [(0, 10), (12, 20), (20, 25), (26, 27), (30, 31)]
    assert tr.idle_between_runs(runs, busy) == [2, 0, 4]


def test_bisected_busy_intervals_agree_with_the_scan():
    import random

    rng = random.Random(3)
    busy = [(s, s + rng.uniform(0.1, 5)) for s in (rng.uniform(0, 200) for _ in range(300))]
    index = tr.Busy(busy)
    for _ in range(200):
        lo = rng.uniform(-5, 205)
        hi = lo + rng.choice([0.0, rng.uniform(0, 3), rng.uniform(0, 40)])
        assert index.clipped(lo, hi) == tr.merge(tr.clip(busy, lo, hi))
        assert index.idle(lo, hi) == tr.idle_intervals(busy, lo, hi)
        assert index.covered(lo, hi) == tr.overlap([(lo, hi)], busy)


def test_collective_time_not_overlapped_by_compute():
    from benchmarks.chip import spans

    ops = [
        ("jit(step)/dot", 0, 10),
        ("jit(step)/sync/all-gather-start", 8, 14),  # 10..14 exposed
        ("jit(step)/sync/all-reduce", 20, 30),  # 20..25 hidden by compute, 25..30 exposed
        ("jit(step)/ffn/convolution", 18, 25),
        ("jit(step)/sync/reduce-scatter", 40, 42),  # outside the step's run
    ]
    dev = tr.Device("/device:TPU:0", ops=[(p.rsplit("/", 1)[1], s, e) for p, s, e in ops],
                    modules=[("jit_step(1)", 0, 31)])
    prog = spans.Program(host=[], devices=[dev], scopes={dev.name: ops})
    assert spans.exposed_time(prog, ["sync"], 0, 35) == (4 + 5) / 1e6
    assert tr.main_program(dev, 0, 35) == "jit_step(1)"


def test_tpu_op_names_keep_name_and_opcode():
    hlo = ("%fusion.608 = (f32[8,12,1024]{2,1,0:T(8,128)}, f32[8,12]{1,0}) fusion(f32[8] %a), "
           "kind=kOutput, calls=%fused_computation.636")
    assert tr.op_name(hlo) == "fusion.608 fusion"
    assert tr.op_name("%convert.48 = bf16[6,768]{1,0:T(8,128)} convert(f32[6,768]{1,0} %p)") == "convert.48 convert"
    assert tr.op_name("dot_general.1") == "dot_general.1"


def test_self_time_leaves_out_nested_operations():
    ops = [("while.1 while", 0, 10), ("fusion.2 fusion", 1, 3), ("fusion.3 fusion", 4, 6), ("copy.4 copy", 12, 13)]
    assert [(n, own) for n, _, _, own in tr.self_times(ops)] == [
        ("while.1 while", 6), ("fusion.2 fusion", 2), ("fusion.3 fusion", 2), ("copy.4 copy", 1)]
    trace = tr.Trace(devices=[tr.Device("/device:TPU:0", ops, [])], host_spans=[])
    assert tr.top_ops(trace, 0, 20, k=2) == [["while.1 while", 6e-9], ["fusion.2 fusion", 2e-9]]


def test_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x) + 1.0)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()

    trace = tr.load(str(tmp_path))
    assert trace.devices and trace.devices[0].ops
    lo, hi = tr.window_of(trace, "bench.window")
    assert (lo, hi) == trace.span("bench.window") and hi > lo
    assert sum(1 for n, _, _ in trace.host_spans if n == "bench.step") == 3
    busy = tr.busy_seconds(trace, lo, hi)
    assert 0 < busy <= (hi - lo) / 1e9
    assert 0 <= tr.mean_idle_share(trace, lo, hi) < 1
    top = tr.top_ops(trace, lo, hi)
    assert top and any("dot" in name for name, _ in top)
    gaps = tr.idle_gaps(trace, lo, hi)
    assert gaps and all(seconds > 0 for _, seconds in gaps)
    assert all(name.startswith("bench.") or name == "unannotated" for name, _ in gaps)
