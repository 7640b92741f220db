"""Reduction of a JAX profiler trace to the numbers per-layer metrics read.

The run traces a few seconds of its window with ``jax.profiler`` and
reads the ``.xplane.pb`` back with ``jax.profiler.ProfileData``. What is
taken from it:

* device op events (the ``XLA Ops`` line of each ``/device:`` plane):
  busy time is the union of their intervals inside the traced window,
  averaged over the devices used;
* program events (the ``XLA Modules`` line): one per run of a compiled
  program, so a program's mean device span per call. A program running
  when the profiler starts or stops shows as a shorter event, the
  device's first or last: a span per call reads only the runs between
  them (:func:`whole_runs`);
* kernel time: the summed duration of the op events whose name or
  ``tf_op``/``long_name`` stat matches a pattern of the name table
  (``bench/kernels.json``, with the names the architecture's module adds
  in its ``KERNELS``), split by the program event that contains them;
* idle gaps: the intervals between busy intervals, each labelled by the
  host-side profiler event that covers most of it.

All times here are ns from the start of the trace session, the same
base for host and device planes.
"""

from __future__ import annotations

import json
import re
from bisect import bisect_right
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
NAME_STATS = ("tf_op", "long_name", "hlo_op", "name")
KERNELS_FILE = Path(__file__).resolve().parent / "kernels.json"


@dataclass
class Event:
    name: str
    start: float             # ns
    dur: float               # ns
    label: str = ""          # name plus its naming stats

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Device:
    name: str
    ops: list = field(default_factory=list)
    modules: list = field(default_factory=list)


@dataclass
class Trace:
    devices: list
    host: list               # host Events, every line
    window_ns: float


def _label(ev) -> str:
    parts = [ev.name]
    try:
        stats = dict(ev.stats)
    except Exception:                                   # noqa: BLE001
        stats = {}
    for k in NAME_STATS:
        v = stats.get(k)
        if isinstance(v, str) and v and v != ev.name:
            parts.append(v)
    return " | ".join(parts)


def load(path, window_ns: float) -> Trace:
    """Read an ``.xplane.pb``; keep what falls inside [0, window_ns]."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            dev = Device(plane.name)
            for line in plane.lines:
                if line.name in OPS_LINES:
                    dev.ops = [Event(e.name, e.start_ns, e.duration_ns,
                                     _label(e)) for e in line.events]
                elif line.name in MODULE_LINES:
                    dev.modules = [Event(e.name, e.start_ns, e.duration_ns,
                                         _label(e)) for e in line.events]
            if dev.ops:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.duration_ns)
                            for e in line.events)
    return Trace(devices, host, window_ns)


def clip(iv, lo: float, hi: float):
    a, b = max(iv[0], lo), min(iv[1], hi)
    return (a, b) if b > a else None


def union(intervals) -> list:
    """Merge [start, end) intervals into disjoint sorted ones."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def busy_ns(dev: Device, window_ns: float) -> float:
    ivs = [c for e in dev.ops if (c := clip((e.start, e.end), 0, window_ns))]
    return sum(b - a for a, b in union(ivs))


def idle_gaps(dev: Device, window_ns: float) -> list:
    """[(start, end)] of every interval in the window with no op."""
    ivs = union(c for e in dev.ops
                if (c := clip((e.start, e.end), 0, window_ns)))
    gaps, t = [], 0.0
    for a, b in ivs:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < window_ns:
        gaps.append((t, window_ns))
    return gaps


def label_gap(gap, host: list) -> str:
    """The host event covering most of ``gap`` (the innermost among
    equals), or 'no host event'."""
    best, best_cover = None, 0.0
    for e in host:
        c = clip((e.start, e.end), *gap)
        if c is None:
            continue
        cover = c[1] - c[0]
        if cover > best_cover or (cover == best_cover and best is not None
                                  and e.dur < best.dur):
            best, best_cover = e, cover
    return best.name if best is not None else "no host event"


def containing(events: list, inner: list) -> list:
    """For each event of ``inner``, the event of ``events`` (sorted,
    disjoint) whose interval contains its start, or None."""
    evs = sorted(events, key=lambda e: e.start)
    starts = [e.start for e in evs]
    out = []
    for x in inner:
        i = bisect_right(starts, x.start) - 1
        out.append(evs[i] if i >= 0 and x.start < evs[i].end else None)
    return out


def load_kernel_table(extra: dict | None = None,
                      path: Path = KERNELS_FILE) -> dict:
    """{"kernels": {kernel: [regex]}, "programs": {program: [regex]},
    "containers": [regex]}: the common table, with ``extra`` (a model
    module's ``KERNELS``, of the same form) merged in: its patterns
    follow the table's under the same name, and its new names follow
    the table's names."""
    table = json.loads(Path(path).read_text())
    for part, more in (extra or {}).items():
        if isinstance(more, dict):
            merged = table.setdefault(part, {})
            for name, pats in more.items():
                merged[name] = merged.get(name, []) + list(pats)
        else:
            table[part] = table.get(part, []) + list(more)
    return table


def match_any(patterns, text: str) -> bool:
    return any(re.search(p, text) for p in patterns)


def whole_runs(dev: Device, window_ns: float) -> list:
    """The program events of ``dev`` in the window that neither the
    profiler's start nor its stop can have cut: all but the device's
    first and last program event. (A whole run there is left out too,
    which costs a mean one sample and no bias.)"""
    mods = sorted(dev.modules, key=lambda m: (m.start, m.end))
    if mods:
        last = max(mods, key=lambda m: m.end)
        mods = [m for m in mods[1:] if m is not last]
    return [m for m in mods if m.start >= 0 and m.end <= window_ns]


@dataclass
class Reduction:
    """What the per-layer readers take from one trace."""
    window_s: float
    busy_s: float                       # mean over devices
    program_spans: dict                 # program name → [seconds]
    whole_spans: dict                   # the same, whole runs only
    kernel_s: dict                      # (kernel, program) → seconds
    kernel_calls: dict                  # (kernel, program) → calls
    top_ops: list                       # [[name, seconds]] ≤ 10
    top_gaps: list                      # [[label, seconds]] ≤ 10


def program_name(module: Event, programs: dict) -> str | None:
    for name, pats in programs.items():
        if match_any(pats, module.label):
            return name
    return None


def short_name(label: str) -> str:
    """``%fused_ffn.4 = f32[...] custom-call(...)`` → ``fused_ffn.4``."""
    head = label.split(" = ", 1)[0].split(" | ", 1)[0]
    return head.lstrip("%")


def reduce(trace: Trace, kernels: dict, programs: dict,
           containers=()) -> Reduction:
    """``programs``: {program name: [regex on the module event]};
    ``kernels``: {kernel name: [regex on the op event label]};
    ``containers``: regexes of ops that only enclose others (a while
    loop), left out of the op ranking."""
    w = trace.window_ns
    n = max(1, len(trace.devices))
    busy = sum(busy_ns(d, w) for d in trace.devices) / n
    spans: dict = defaultdict(list)
    whole: dict = defaultdict(list)
    ksec: dict = defaultdict(float)
    kcalls: dict = defaultdict(int)
    op_time: dict = defaultdict(float)
    gaps = []
    for dev in trace.devices:
        mods = [m for m in dev.modules if clip((m.start, m.end), 0, w)]
        for m in mods:
            if m.start >= 0 and m.end <= w:
                p = program_name(m, programs)
                if p:
                    spans[p].append(m.dur / 1e9)
        for m in whole_runs(dev, w):
            p = program_name(m, programs)
            if p:
                whole[p].append(m.dur / 1e9)
        for op, mod in zip(dev.ops, containing(mods, dev.ops)):
            if not (op.start >= 0 and op.end <= w):
                continue
            if not match_any(containers, op.label):
                op_time[short_name(op.label)] += op.dur / n / 1e9
            prog = program_name(mod, programs) if mod is not None else None
            for kname, pats in kernels.items():
                if match_any(pats, op.label):
                    ksec[(kname, prog)] += op.dur / 1e9
                    kcalls[(kname, prog)] += 1
                    break
        gaps.extend(idle_gaps(dev, w))
    gaps.sort(key=lambda g: g[0] - g[1])
    top_gaps = [[label_gap(g, trace.host), (g[1] - g[0]) / 1e9]
                for g in gaps[:10]]
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return Reduction(window_s=w / 1e9, busy_s=busy / 1e9,
                     program_spans=dict(spans), whole_spans=dict(whole),
                     kernel_s=dict(ksec),
                     kernel_calls=dict(kcalls),
                     top_ops=[[k, v] for k, v in top_ops],
                     top_gaps=top_gaps)
