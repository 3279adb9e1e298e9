"""The program's own annotations in a profiler trace, and what they name.

``trace.load`` keeps the benchmark's host spans (``bench.``) and each device
operation's name.  This module reads the same ``.xplane.pb`` again, once per
process, and keeps what the program writes there itself:

* its host spans, whose names start with ``repro.`` (the runtime loop's
  ``repro.train.*`` phases, ``runtime/trainer.py``);
* per device, each operation with its named-scope path (``jax.named_scope``
  in the model, the optimizer and the cross-pod sync).  A TPU trace keeps
  the path as the ``tf_op`` stat of the operation's event metadata
  (``XPlane.event_metadata``), which ``ProfileData`` does not expose; so
  ``scope_paths`` takes it from the file's metadata, a few thousand
  entries, and skips the events.

On a CPU-only trace the XLA CPU client's worker threads stand in as one
device, as in ``trace.load``, and its programs' runs are told apart by their
``run_id``; such a trace carries no scope path.  Where the trace holds no
program span (a program from before the spans) or no scope path, the readers
read nothing.

The readings are per step, over the window's runs of the step program,
found as ``step_gap_ms.train`` finds them: the module that took most of the
device's time in the window.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import re
from typing import Dict, Iterable, List, Optional, Tuple

from benchmarks.chip import harness
from benchmarks.chip import trace as tr

PROGRAM_PREFIX = "repro."
SCOPE_STAT = "tf_op"
_WRAPPED = re.compile(r"[\w.\-]+\((.*)\)")


@dataclasses.dataclass
class Program:
    host: List[tr.Event]  # the program's host spans
    devices: List[tr.Device]  # operations and program runs, per device
    scopes: Dict[str, List[tr.Event]]  # per device: (scope path or "", start, end) of each operation


def scope_names(path: str) -> List[str]:
    """The components of an ``op_name`` path with the transformations peeled
    off: ``jit(inner)/transpose(jvp(head))/dot_general`` ->
    ``["inner", "head", "dot_general"]``."""
    out = []
    for comp in path.split("/"):
        while (m := _WRAPPED.fullmatch(comp)) is not None:
            comp = m.group(1)
        out.append(comp)
    return out


@functools.lru_cache(maxsize=None)
def _components(path: str) -> frozenset:
    return frozenset(scope_names(path))


def under(path: str, scopes: Iterable[str]) -> bool:
    """Whether one of ``scopes`` is a whole component of ``path``."""
    return not _components(path).isdisjoint(scopes)


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one serialized protobuf
    message: a varint as an int, anything else as a memoryview."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, value


def scope_paths(data: bytes) -> Dict[str, Dict[str, str]]:
    """Per device plane of a serialized ``XSpace``, each operation's name to
    its ``SCOPE_STAT`` path (``jit(inner)/transpose(jvp(head))/dot_general``).

    Fields of ``tsl/profiler/protobuf/xplane.proto``: ``XSpace.planes`` 1;
    ``XPlane.name`` 2, ``.event_metadata`` 4 and ``.stat_metadata`` 5, maps
    (key 1, value 2); ``XEventMetadata.name`` 2 and ``.stats`` 5;
    ``XStatMetadata.name`` 2; ``XStat.metadata_id`` 1, ``.str_value`` 5 and
    ``.ref_value`` 7 (a string kept once, as a stat metadata's name).
    A name that two operations share with different paths maps to ``""``.
    """
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(memoryview(data)):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                events.append(dict(_fields(v)).get(2, b""))
            elif f == 5:
                entry = dict(_fields(v))
                stat_names[entry.get(1, 0)] = bytes(dict(_fields(entry.get(2, b""))).get(2, b"")).decode()
        if not name.startswith("/device:"):
            continue
        key = next((k for k, n in stat_names.items() if n == SCOPE_STAT), None)
        paths: Dict[str, str] = {}
        for meta in events:
            op, path = "", None
            for f, v in _fields(meta):
                if f == 2:
                    op = bytes(v).decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) == key:
                        path = bytes(stat[5]).decode() if 5 in stat else stat_names.get(stat.get(7), "")
            if path is not None:
                path = path.rstrip(":")
                paths[op] = path if paths.get(op, path) == path else ""
        out[name] = paths
    return out


def _runs_by_id(ops) -> List[tr.Event]:
    """CPU programs: each run spans the operations that share its ``run_id``."""
    runs: Dict[Tuple[str, str], List[float]] = {}
    for module, run_id, s, e in ops:
        r = runs.setdefault((module, run_id), [s, e])
        r[0], r[1] = min(r[0], s), max(r[1], e)
    return sorted(((m, s, e) for (m, _), (s, e) in runs.items()), key=lambda ev: ev[1])


@functools.lru_cache(maxsize=1)
def read(path: str) -> Program:
    """The program's annotations in the trace file at ``path``."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        data = f.read()
    scope_of = scope_paths(data)
    host: List[tr.Event] = []
    devices: List[tr.Device] = []
    scopes: Dict[str, List[tr.Event]] = {}
    cpu_ops: List[tr.Event] = []
    cpu_runs = []
    for plane in ProfileData.from_serialized_xspace(data).planes:
        if plane.name.startswith("/device:"):
            ops, modules, paths = [], [], []
            plane_scopes = scope_of.get(plane.name, {})
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = tr._events(line)
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        if ev.duration_ns > 0:
                            s, e = float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns)
                            ops.append((tr.op_name(ev.name), s, e))
                            paths.append((plane_scopes.get(ev.name, ""), s, e))
            if ops:
                devices.append(tr.Device(plane.name, ops, modules))
                scopes[plane.name] = paths
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                cpu = line.name.startswith("tf_XLAPjRtCpuClient")
                for ev in line.events:
                    if ev.duration_ns <= 0:
                        continue
                    s, e = float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns)
                    if ev.name.startswith(PROGRAM_PREFIX):
                        host.append((ev.name, s, e))
                    elif cpu and "::" not in ev.name:
                        stats = dict(ev.stats)
                        cpu_ops.append((ev.name, s, e))
                        if "run_id" in stats:
                            cpu_runs.append((str(stats.get("hlo_module", "")), str(stats["run_id"]), s, e))
    if not devices and cpu_ops:
        devices.append(tr.Device("/host:CPU", sorted(cpu_ops, key=lambda ev: ev[1]), _runs_by_id(cpu_runs)))
    devices.sort(key=lambda d: d.name)
    return Program(host=sorted(host, key=lambda ev: ev[1]), devices=devices, scopes=scopes)


def of(rec) -> Optional[Program]:
    """The program's annotations in the run's trace; ``None`` untraced."""
    if rec.trace is None:
        return None
    try:
        path = tr.find_xplane(str(harness.WORK / "trace"))
    except FileNotFoundError:
        return None
    return read(path)


def step_runs(dev: tr.Device, lo: float, hi: float) -> List[tr.Interval]:
    """The runs of the step program within [lo, hi]."""
    step = tr.main_program(dev, lo, hi)
    return tr.runs_within(dev, step, lo, hi) if step else []


def idle_under(rec, span: str) -> Optional[float]:
    """Device-idle ms between consecutive step runs in the run's window
    during which the host was inside ``span`` (``gap_idle``)."""
    prog = of(rec)
    return None if prog is None else gap_idle(prog, span, *rec.window_ns)


def exposed_ms(rec, scopes: Iterable[str]) -> Optional[float]:
    """Device ms per step run in the run's window in which only operations
    under ``scopes`` run (``exposed_time``)."""
    prog = of(rec)
    return None if prog is None else exposed_time(prog, scopes, *rec.window_ns)


def scope_ms(rec, scopes: Iterable[str]) -> Optional[float]:
    """Device ms per step run in the run's window under ``scopes``
    (``scope_time``)."""
    prog = of(rec)
    return None if prog is None else scope_time(prog, scopes, *rec.window_ns)


def gap_idle(prog: Program, span: str, lo: float, hi: float) -> Optional[float]:
    """Device-idle time between consecutive step runs in [lo, hi] during
    which the host was inside a ``span``, in ms per gap, averaged over the
    devices; ``None`` where the trace holds no such span."""
    covered = [(s, e) for n, s, e in prog.host if n == span]
    if not covered:
        return None
    per = []
    for dev in prog.devices:
        runs = step_runs(dev, lo, hi)
        if len(runs) < 2:
            continue
        busy = tr.Busy(dev.busy())
        idle = [g for a, b in zip(runs, runs[1:]) for g in busy.idle(a[1], max(a[1], b[0]))]
        per.append(tr.overlap(idle, covered) / (len(runs) - 1))
    return sum(per) / len(per) / 1e6 if per else None


def scope_time(prog: Program, scopes: Iterable[str], lo: float, hi: float) -> Optional[float]:
    """Device self time of the operations of the step runs in [lo, hi] whose
    path holds one of ``scopes``, in ms per run, averaged over the devices;
    ``None`` where no such operation carries one of them."""
    scopes = set(scopes)
    per = []
    for dev in prog.devices:
        runs = step_runs(dev, lo, hi)
        ops = prog.scopes.get(dev.name)
        if not runs or not ops:
            continue
        starts = [s for s, _ in runs]
        total, found = 0.0, False
        for path, s, _, own in tr.self_times(ops):
            k = bisect.bisect_right(starts, s) - 1
            if k >= 0 and s < runs[k][1] and under(path, scopes):
                total, found = total + own, True
        if found:
            per.append(total / len(runs))
    return sum(per) / len(per) / 1e6 if per else None


def own_intervals(ops: Iterable[tr.Event]) -> List[Tuple[str, List[tr.Interval]]]:
    """Each operation with the intervals of its own time: its span less the
    operations nested in it (a loop's body runs inside the loop's event).
    An operation that overlaps another without lying inside it is not nested."""
    order = sorted(ops, key=lambda ev: (ev[1], -ev[2]))
    out: List[Tuple[str, float, float, List[tr.Interval]]] = []
    stack: List[int] = []
    for name, s, e in order:
        while stack and out[stack[-1]][2] < e:
            stack.pop()
        if stack:
            out[stack[-1]][3].append((s, e))
        out.append((name, s, e, []))
        stack.append(len(out) - 1)
    return [(name, tr.idle_intervals(inner, s, e) if inner else [(s, e)]) for name, s, e, inner in out]


def exposed_time(prog: Program, scopes: Iterable[str], lo: float, hi: float) -> Optional[float]:
    """Device time of the step runs in [lo, hi] in which an operation whose
    path holds one of ``scopes`` runs and no other operation does, each
    operation taken by its own time (``own_intervals``), in ms per run,
    averaged over the devices; ``None`` where no operation of a step run
    carries one of them."""
    scopes = set(scopes)
    per = []
    for dev in prog.devices:
        runs = step_runs(dev, lo, hi)
        ops = prog.scopes.get(dev.name)
        if not runs or not ops:
            continue
        starts = [s for s, _ in runs]
        inside, outside = [], []
        for path, own in own_intervals(ops):
            if not under(path, scopes):
                outside.extend(own)
                continue
            for s, e in own:
                k = bisect.bisect_right(starts, s) - 1
                if k >= 0 and s < runs[k][1]:
                    inside.append((s, min(e, runs[k][1])))
        if inside:
            per.append(tr.uncovered(inside, outside) / len(runs))
    return sum(per) / len(per) / 1e6 if per else None
