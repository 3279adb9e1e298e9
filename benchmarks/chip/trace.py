"""Reduction of a JAX profiler trace to device intervals, and their arithmetic.

``load`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` wrote and keeps
three things, all in nanoseconds on the trace's own clock:

* per device, the operations that ran on it (the ``XLA Ops`` line of a
  ``/device:`` plane) and the programs they belong to (``XLA Modules``);
* the host spans that the benchmark itself annotated (names starting with
  ``bench.``), so that an idle gap can be told apart by what the host did.

On a CPU-only trace there is no device plane; the XLA CPU client's worker
threads then stand in as one device, so that the reduction can be tested
without a chip.

The rest are pure functions over ``(start, end)`` intervals.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]  # (name, start_ns, end_ns)

HOST_PREFIX = "bench."


# -- interval arithmetic ---------------------------------------------------------


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of the intervals (empty ones dropped)."""
    out: List[List[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Iterable[Interval]) -> float:
    """Length of the union of the intervals."""
    return sum(e - s for s, e in merge(intervals))


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def overlap(a: Iterable[Interval], b: Iterable[Interval]) -> float:
    """Length of the part of union(a) that union(b) covers."""
    a, b = merge(a), merge(b)
    total, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def uncovered(a: Iterable[Interval], b: Iterable[Interval]) -> float:
    """Length of union(a) that union(b) does not cover."""
    a = merge(a)
    return length(a) - overlap(a, b)


def idle_share(busy: Iterable[Interval], lo: float, hi: float) -> float:
    """1 - (union of busy within [lo, hi]) / (hi - lo)."""
    return 1.0 - length(clip(busy, lo, hi)) / (hi - lo)


def idle_intervals(busy: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of ``busy`` within ``[lo, hi]``."""
    out, t = [], lo
    for s, e in merge(clip(busy, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class Busy:
    """Intervals merged once, with their ends kept for bisection, so that the
    part of them within a short span is found without scanning them all."""

    def __init__(self, intervals: Iterable[Interval]):
        self.merged = merge(intervals)
        self.ends = [e for _, e in self.merged]

    def clipped(self, lo: float, hi: float) -> List[Interval]:
        """``clip(merged, lo, hi)``."""
        out: List[Interval] = []
        if hi <= lo:
            return out
        for k in range(bisect.bisect_right(self.ends, lo), len(self.merged)):
            s, e = self.merged[k]
            if s >= hi:
                break
            out.append((max(s, lo), min(e, hi)))
        return out

    def covered(self, lo: float, hi: float) -> float:
        """Length of [lo, hi] that the intervals cover."""
        return sum(e - s for s, e in self.clipped(lo, hi))

    def idle(self, lo: float, hi: float) -> List[Interval]:
        """``idle_intervals(merged, lo, hi)``."""
        out, t = [], lo
        for s, e in self.clipped(lo, hi):
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if hi > t:
            out.append((t, hi))
        return out


# -- the trace --------------------------------------------------------------------


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Event]
    modules: List[Event]

    def busy(self) -> List[Interval]:
        return merge((s, e) for _, s, e in self.ops)

    def runs_of(self, program: str) -> List[Interval]:
        """Executions of the program whose module name contains ``program``."""
        return sorted((s, e) for n, s, e in self.modules if program in n)


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    host_spans: List[Event]

    def span(self, name: str) -> Optional[Interval]:
        """The first host span called ``name``."""
        for n, s, e in self.host_spans:
            if n == name:
                return s, e
        return None


@functools.lru_cache(maxsize=None)  # a step's few thousand names recur in every step
def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[8,1024]{...} fusion(...), kind=...`` -> ``fusion.12 fusion``.

    A TPU trace names each operation by its whole HLO instruction; keep the
    instruction's name and its opcode.  Other names pass unchanged."""
    head, sep, rest = hlo.partition(" = ")
    if not sep or not head.startswith("%"):
        return hlo
    i = 0
    if rest.startswith("("):  # a tuple shape: skip to its closing parenthesis
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                break
    j = rest.find(" ", i)
    opcode = rest[j + 1 :].split("(", 1)[0] if j >= 0 else ""
    return f"{head[1:]} {opcode}".strip()


def _events(line) -> List[Event]:
    out = []
    for ev in line.events:
        d = float(ev.duration_ns)
        if d > 0:
            s = float(ev.start_ns)
            out.append((op_name(ev.name), s, s + d))
    return out


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(log_dir: str) -> Trace:
    """Read the newest trace under ``log_dir``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(log_dir))
    devices: List[Device] = []
    host: List[Event] = []
    cpu_ops: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = _events(line)
                elif line.name == "XLA Modules":
                    modules = _events(line)
            if ops:
                devices.append(Device(plane.name, ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = _events(line)
                host.extend(e for e in evs if e[0].startswith(HOST_PREFIX))
                if line.name.startswith("tf_XLAPjRtCpuClient"):
                    cpu_ops.extend(e for e in evs if "::" not in e[0])
    if not devices and cpu_ops:
        devices.append(Device("/host:CPU", sorted(cpu_ops, key=lambda e: e[1]), []))
    devices.sort(key=lambda d: d.name)
    return Trace(devices=devices, host_spans=sorted(host, key=lambda e: e[1]))


# -- summaries --------------------------------------------------------------------


def window_of(trace: Trace, span_name: str) -> Interval:
    """The traced window: the benchmark's window span where the trace holds
    it, else from the first to the last device operation."""
    span = trace.span(span_name)
    if span is not None:
        return span
    starts = [s for d in trace.devices for _, s, _ in d.ops]
    ends = [e for d in trace.devices for _, _, e in d.ops]
    return min(starts), max(ends)


def busy_seconds(trace: Trace, lo: float, hi: float) -> float:
    """Device-busy seconds within [lo, hi], averaged over the devices."""
    per = [length(clip(d.busy(), lo, hi)) for d in trace.devices]
    return sum(per) / len(per) / 1e9


def self_times(ops: Sequence[Event]) -> List[Tuple[str, float, float, float]]:
    """Each operation with its self time: its span less the operations nested
    in it (a loop's body runs inside the loop's own event)."""
    order = sorted(ops, key=lambda ev: (ev[1], -ev[2]))
    out: List[List] = []
    stack: List[int] = []
    for name, s, e in order:
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]][3] -= min(e, out[stack[-1]][2]) - s
        out.append([name, s, e, e - s])
        stack.append(len(out) - 1)
    return [tuple(x) for x in out]


def top_ops(trace: Trace, lo: float, hi: float, k: int = 10) -> List[List]:
    """The ``k`` operations that took most device time in [lo, hi] by self
    time, in seconds averaged over the devices."""
    total: Dict[str, float] = defaultdict(float)
    for d in trace.devices:
        for n, s, e, own in self_times(d.ops):
            if lo <= s < hi:
                total[n] += own / 1e9
    n_dev = len(trace.devices)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t / n_dev] for n, t in ranked]


def host_activity(trace: Trace, gap: Interval) -> str:
    """The innermost benchmark span covering the middle of ``gap``."""
    mid = (gap[0] + gap[1]) / 2
    best: Optional[Event] = None
    for ev in trace.host_spans:
        if ev[1] <= mid < ev[2] and (best is None or ev[2] - ev[1] < best[2] - best[1]):
            best = ev
    return best[0] if best else "unannotated"


def idle_gaps(trace: Trace, lo: float, hi: float, k: int = 10) -> List[List]:
    """The ``k`` longest idle gaps of the first device in [lo, hi], each
    named by what the benchmark's host spans say the host was doing."""
    dev = trace.devices[0]
    gaps = sorted(idle_intervals(dev.busy(), lo, hi), key=lambda g: g[0] - g[1])[:k]
    return [[host_activity(trace, g), (g[1] - g[0]) / 1e9] for g in gaps]


def main_program(device: Device, lo: float, hi: float) -> Optional[str]:
    """The module that took most of ``device``'s time in [lo, hi]."""
    total: Dict[str, float] = defaultdict(float)
    for n, s, e in device.modules:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            total[n] += e - s
    return max(total, key=total.get) if total else None


def runs_within(device: Device, program: str, lo: float, hi: float) -> List[Interval]:
    return [r for r in device.runs_of(program) if lo <= r[0] and r[1] <= hi]


def idle_between_runs(runs: Sequence[Interval], busy: Sequence[Interval]) -> List[float]:
    """For each pair of consecutive runs, the idle time between them: the
    gap from one's end to the next one's start, less any operation in it."""
    runs = sorted(runs)
    busy = Busy(busy)
    out = []
    for a, b in zip(runs, runs[1:]):
        gap = (a[1], max(a[1], b[0]))
        out.append((gap[1] - gap[0]) - busy.covered(*gap))
    return out


def mean_idle_share(trace: Trace, lo: float, hi: float) -> float:
    """1 - busy / window, averaged over the devices."""
    return sum(idle_share(d.busy(), lo, hi) for d in trace.devices) / len(trace.devices)
