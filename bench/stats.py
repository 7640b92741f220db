"""End-to-end metric arithmetic over the load generator's records.

Every number is taken over all samples of the window, never from
medians of chunks: a rate is every token that arrived in the window
over the window's length, so a stall inside the window lowers it, and a
tail is the nearest-rank percentile of every sample.
"""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least q% of
    the samples at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    return xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)]


def ok(rec: dict) -> bool:
    """A request that streamed every token it asked for and ended."""
    return (rec.get("status") == 200 and rec.get("done")
            and not rec.get("error")
            and len(rec.get("tokens", ())) == rec["max_tokens"])


def in_window(t: float, run: dict) -> bool:
    return run["t_start"] <= t < run["t_end"]


def output_tokens(run: dict) -> int:
    return sum(in_window(t, run) for r in run["records"]
               for t in r.get("stamps", ()))


def output_tok_s(run: dict) -> float:
    return output_tokens(run) / (run["t_end"] - run["t_start"])


def itl_ms(run: dict) -> list[float]:
    """Every inter-token gap (SSE frame to frame) that ends in the
    window, in ms."""
    return [(b - a) * 1e3 for r in run["records"]
            for a, b in zip(r.get("stamps", ()), r.get("stamps", ())[1:])
            if in_window(b, run)]


def due_in_window(run: dict) -> list[dict]:
    return [r for r in run["records"] if in_window(r["due"], run)]


def ttft_ms(run: dict) -> list[float]:
    """Time to first token of every request due in the window, from its
    due time, in ms. A request with no token has none."""
    return [(r["stamps"][0] - r["due"]) * 1e3 for r in due_in_window(run)
            if r.get("stamps")]


def lateness_ms(run: dict) -> list[float]:
    return [(r["sent"] - r["due"]) * 1e3 for r in due_in_window(run)
            if "sent" in r]


def end_to_end(run: dict) -> dict:
    """The end-to-end metrics this module knows, by name."""
    itl = itl_ms(run)
    ttft = ttft_ms(run)
    out = {"output_tok_s": output_tok_s(run)}
    if itl:
        out["itl_p50_ms"] = percentile(itl, 50)
        out["itl_p95_ms"] = percentile(itl, 95)
    if ttft:
        out["ttft_p50_ms"] = percentile(ttft, 50)
        out["ttft_p95_ms"] = percentile(ttft, 95)
    return out
