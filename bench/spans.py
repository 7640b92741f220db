"""Readings of the names the program gives its own work in a trace.

The program writes three kinds of name into the profiler trace of a
``--trace 1`` run:

* host spans ``repro.*`` around each phase of the serve cycle
  (``repro.serving.metrics.SPANS``), on the host planes;
* the mark ``scope`` = ``lop_screen`` among the frontend attributes of
  the device ops of the decode attention's screen phase (the screen
  launch and its candidate selection), which a TPU trace shows in the
  instruction text it names each op by;
* the prefill-chunk program's name, ``jit_prefill_chunk``, on its
  module events.

Programs are found by the name table's ``programs`` patterns
(``bench/kernels.json`` merged with the architecture module's
``KERNELS``: ``harness.Context.table``).

The readers of ``lop_screen_ms``, ``prefill_chunk_ms`` and
``cycle_idle_ms`` share what is here. A program that writes none of
these names reads None, not zero.
"""

from __future__ import annotations

import re
from bisect import bisect_right
from collections import defaultdict
from dataclasses import dataclass
from functools import lru_cache

from bench import trace as T
from bench.harness import log

SPAN_PREFIX = "repro."
SCREEN_SCOPE = re.compile(r"\blop_screen\b")


@lru_cache(maxsize=1)
def _load(path: str, window_ns: float) -> T.Trace:
    tr = T.load(path, window_ns)
    return T.Trace(tr.devices, [e for e in tr.host
                                if e.name.startswith(SPAN_PREFIX)],
                   tr.window_ns)


def trace(ctx) -> T.Trace:
    """The run's trace, its host events cut to the program's spans; read
    once per run, whichever reader asks first."""
    during = ctx.run["during"]
    return _load(str(during.path()), (during.t1 - during.t0) * 1e9)


def _inside(e: T.Event, window_ns: float) -> bool:
    return e.start >= 0 and e.end <= window_ns


def lop_screen_ms(tr: T.Trace, programs: dict):
    """Device time of the ops marked ``lop_screen`` inside
    decode-step programs that lie wholly in the window (the union of
    their intervals, so an op nested in another counts once), over the
    number of those programs, in ms; None where no such op ran."""
    pats = programs["decode_step"]
    screen_ns, steps = 0.0, 0
    for dev in tr.devices:
        mods = [m for m in dev.modules
                if _inside(m, tr.window_ns) and T.match_any(pats, m.label)]
        steps += len(mods)
        per_mod = defaultdict(list)
        for op, mod in zip(dev.ops, T.containing(mods, dev.ops)):
            if mod is not None and SCREEN_SCOPE.search(op.label):
                iv = T.clip((op.start, op.end), mod.start, mod.end)
                if iv:
                    per_mod[id(mod)].append(iv)
        screen_ns += sum(b - a for ivs in per_mod.values()
                         for a, b in T.union(ivs))
    if not steps or screen_ns <= 0:
        return None
    return screen_ns / steps / 1e6


def prefill_chunk_ms(tr: T.Trace, programs: dict):
    """Mean device span of the prefill-chunk program's whole runs in the
    window (``trace.whole_runs``: none that the profiler's start or stop
    cut), in ms; None where there is none."""
    pats = programs["prefill_chunk"]
    spans = [m.dur for dev in tr.devices
             for m in T.whole_runs(dev, tr.window_ns)
             if T.match_any(pats, m.label)]
    if not spans:
        return None
    return sum(spans) / len(spans) / 1e6


def _busy(dev: T.Device, window_ns: float) -> list:
    return T.union(c for e in dev.ops
                   if (c := T.clip((e.start, e.end), 0, window_ns)))


def _idle_within(busy: list, starts: list, lo: float, hi: float) -> list:
    """[(start, end)] of [lo, hi) that no interval of ``busy`` (sorted,
    disjoint, ``starts`` their starts) covers."""
    out, t = [], lo
    for a, b in busy[max(0, bisect_right(starts, lo) - 1):]:
        if a >= hi:
            break
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def _innermost_split(piece, spans: list) -> dict:
    """{span name: ns} of ``piece`` by the shortest span covering each
    part of it (the spans of one thread nest, so the shortest is the
    innermost)."""
    lo, hi = piece
    over = [s for s in spans if s.start < hi and s.end > lo]
    cuts = sorted({lo, hi} | {t for s in over for t in (s.start, s.end)
                              if lo < t < hi})
    out: dict = defaultdict(float)
    for a, b in zip(cuts, cuts[1:]):
        cover = [s for s in over if s.start <= a and s.end >= b]
        name = (min(cover, key=lambda s: s.dur).name if cover
                else "no span")
        out[name] += b - a
    return out


@dataclass
class CycleIdle:
    cycles: int             # repro.cycle spans in the window with a decode
    mean_ms: float          # device idle inside one of them
    split_ms: dict          # innermost repro.* span → idle ms a cycle
    window_idle_ms: float   # device idle in the whole window


def cycle_idle(tr: T.Trace) -> CycleIdle | None:
    """Device idle inside the ``repro.cycle`` spans that lie wholly in
    the window and hold a ``repro.decode.dispatch``, averaged over the
    devices; None where there is no such cycle or no device."""
    w = tr.window_ns
    cycles = sorted((e for e in tr.host if e.name == "repro.cycle"
                     and _inside(e, w)), key=lambda e: e.start)
    dispatches = [e for e in tr.host if e.name == "repro.decode.dispatch"]
    held = {id(c) for c in T.containing(cycles, dispatches) if c is not None}
    cycles = [c for c in cycles if id(c) in held]
    if not cycles or not tr.devices:
        return None
    spans = [e for e in tr.host if e.name.startswith(SPAN_PREFIX)]
    split: dict = defaultdict(float)
    idle_ns = window_idle_ns = 0.0
    for dev in tr.devices:
        busy = _busy(dev, w)
        starts = [a for a, _ in busy]
        window_idle_ns += sum(b - a for a, b in
                              _idle_within(busy, starts, 0, w))
        for c in cycles:
            for piece in _idle_within(busy, starts, c.start, c.end):
                idle_ns += piece[1] - piece[0]
                for name, ns in _innermost_split(piece, spans).items():
                    split[name] += ns
    n_dev = len(tr.devices)
    per_cycle = n_dev * len(cycles) * 1e6
    return CycleIdle(
        cycles=len(cycles), mean_ms=idle_ns / per_cycle,
        split_ms={k: v / per_cycle
                  for k, v in sorted(split.items(), key=lambda kv: -kv[1])},
        window_idle_ms=window_idle_ns / n_dev / 1e6)


def cycle_idle_ms(tr: T.Trace):
    """:func:`cycle_idle`'s mean, with its split logged."""
    got = cycle_idle(tr)
    if got is None:
        return None
    parts = ", ".join(f"{k} {v:.3f}" for k, v in got.split_ms.items())
    log(f"cycle idle: {got.mean_ms:.3f} ms in each of {got.cycles} decode "
        f"cycles ({parts}), {got.mean_ms * got.cycles:.3f} ms in all, of "
        f"{got.window_idle_ms:.3f} ms device idle in the window")
    return got.mean_ms
