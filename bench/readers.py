"""What the per-layer metric readers share (``bench/metrics/*.py``).

A reader is ``read(ctx) -> float | None`` (see ``harness.Context``); it
returns None when its run holds nothing to read, and the harness then
leaves the metric out of the result line. Counts of the architecture's
work come from its model module, ``ctx.model`` (``bench/models/``).
"""

from __future__ import annotations

from bench import cost
from bench.reference import model as R


def program_mean_ms(ctx, program: str):
    """Mean device span of the program's whole runs in the traced span
    (``trace.whole_runs``), in ms."""
    spans = ctx.reduction.whole_spans.get(program)
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)


def kernel_seconds(ctx, kernel: str, program: str) -> tuple[float, int]:
    key = (kernel, program)
    return (ctx.reduction.kernel_s.get(key, 0.0),
            ctx.reduction.kernel_calls.get(key, 0))


def decoded_live_lengths(ctx) -> list[int]:
    """The live cache length of every decode step lane whose token
    reached the client inside the traced span: token i ≥ 1 of a request
    with prompt P was decoded at P + i valid positions."""
    t0, t1 = ctx.trace_span
    return [r["prompt_len"] + i
            for r in ctx.run["records"]
            for i, t in enumerate(r.get("stamps", ())) if i and t0 <= t < t1]


def k_keep(ctx) -> int:
    arch = ctx.conf["arch"]
    cap = R.capacity_of(arch, ctx.conf["server"]["max_len"])
    return R.k_keep_blocks(arch, cap)


def peaks(ctx) -> dict:
    return cost.peaks(ctx.device_kind)
