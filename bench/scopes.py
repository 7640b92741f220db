"""Device time of the ops a program marks with a scope
(``repro.core.marks``): a TPU trace shows the marks in the instruction
text it names each op by, as ``scope=<name>`` or, for a scope nested in
others, ``within=<outer>/...``; a kernel launch carries them in its
``kernel_metadata``. The readers of ``moe_experts_ms``,
``moe_experts_roofline`` and ``mla_attention_roofline`` share this.
"""

from __future__ import annotations

import re
from collections import defaultdict

from bench import trace as T


def _inside(e: T.Event, window_ns: float) -> bool:
    return e.start >= 0 and e.end <= window_ns


def scope_ms(tr: T.Trace, programs: dict, name: str,
             program: str = "decode_step"):
    """(ms per program run, runs): device time of the ops marked
    ``name`` inside the runs of ``program`` that lie wholly in the
    window (the union of their intervals, so an op nested in another
    counts once), over the number of those runs; None where no such op
    ran."""
    mark = re.compile(rf"\b{re.escape(name)}\b")
    pats = programs[program]
    marked_ns, runs = 0.0, 0
    for dev in tr.devices:
        mods = [m for m in dev.modules
                if _inside(m, tr.window_ns) and T.match_any(pats, m.label)]
        runs += len(mods)
        per_mod = defaultdict(list)
        for op, mod in zip(dev.ops, T.containing(mods, dev.ops)):
            if mod is not None and mark.search(op.label):
                iv = T.clip((op.start, op.end), mod.start, mod.end)
                if iv:
                    per_mod[id(mod)].append(iv)
        marked_ns += sum(b - a for ivs in per_mod.values()
                         for a, b in T.union(ivs))
    if not runs or marked_ns <= 0:
        return None
    return marked_ns / runs / 1e6, runs
