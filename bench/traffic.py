"""The one traffic generator: a mix file of parameters → requests.

A mix (``bench/traffic/<name>.json``) holds only data:

    {"loop": "closed", "clients": 8,
     "prompt_len": {"dist": "uniform", "lo": 1536, "hi": 1792},
     "max_tokens": {"dist": "uniform", "lo": 192, "hi": 256},
     "grid": 16}

    {"loop": "open", "rate": 9.6,
     "prompt_len": {"dist": "lognormal", "median": 48, "sigma": 0.5,
                    "lo": 16, "hi": 128},
     "max_tokens": {...}}

``closed``: ``clients`` callers, each sending its next request when the
previous one has finished. ``open``: arrivals at ``rate`` per second,
sent when due whatever the server does.

Every seed gets the same set of sizes and arrivals in another order, so
the seed changes which request comes when, not how much work a window
holds: a distribution is sampled at the ``n`` midpoint quantiles
(i + 0.5) / n and the seed permutes them. A closed-loop client cycles
through its own permutation of a ``grid``-point set; an open loop draws
``round(rate · seconds)`` inter-arrival gaps from the exponential
distribution's quantiles. Prompt token ids are uniform over the
vocabulary, drawn from (seed, stream, index), so prompts share no
prefix. Requests are greedy with no end-of-sequence token: each decodes
exactly ``max_tokens`` tokens.

Imports numpy and the standard library only: the load-generating child
process uses it and never imports JAX.
"""

from __future__ import annotations

import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str, directory: Path = DIR) -> dict:
    path = Path(directory) / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {path}")
    mix = json.loads(path.read_text())
    if mix.get("loop") not in ("closed", "open"):
        raise ValueError(f"{path}: loop must be 'closed' or 'open'")
    return mix


def quantiles(spec: dict, n: int) -> list[int]:
    """The ``n`` midpoint quantiles of an integer length distribution."""
    u = [(i + 0.5) / n for i in range(n)]
    if spec["dist"] == "uniform":
        lo, hi = spec["lo"], spec["hi"]
        return [lo + min(int(p * (hi - lo + 1)), hi - lo) for p in u]
    if spec["dist"] == "lognormal":
        nd = NormalDist()
        mu, sigma = math.log(spec["median"]), spec["sigma"]
        vals = [round(math.exp(mu + sigma * nd.inv_cdf(p))) for p in u]
        return [min(max(v, spec["lo"]), spec["hi"]) for v in vals]
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2**63, seed >> 63, *stream])


def _permuted(values: list, seed: int, *stream: int) -> list:
    order = _rng(seed, *stream).permutation(len(values))
    return [values[i] for i in order]


def max_prompt(mix: dict) -> int:
    return max(quantiles(mix["prompt_len"], 64)
               + [mix["prompt_len"].get("hi", 0)])


def max_tokens(mix: dict) -> int:
    return max(quantiles(mix["max_tokens"], 64)
               + [mix["max_tokens"].get("hi", 0)])


def prompt(seed: int, stream: int, index: int, length: int,
           vocab: int) -> list[int]:
    """Token ids of request ``index`` of ``stream`` (a client, or the
    open loop's single stream)."""
    return _rng(seed, 1, stream, index).integers(
        0, vocab, length).tolist()


def closed_sizes(mix: dict, seed: int, client: int,
                 index: int) -> tuple[int, int]:
    """(prompt length, max_tokens) of a closed-loop client's
    ``index``-th request."""
    g = mix.get("grid", 16)
    pl = _permuted(quantiles(mix["prompt_len"], g), seed, 2, client)
    mt = _permuted(quantiles(mix["max_tokens"], g), seed, 3, client)
    return pl[index % g], mt[index % g]


def open_schedule(mix: dict, seed: int, seconds: float) -> list[dict]:
    """The open loop's arrivals: [{"at": s from window start, "prompt_len",
    "max_tokens"}] for every request due inside the window."""
    n = max(1, round(mix["rate"] * seconds))
    gaps = [-math.log(1.0 - (i + 0.5) / n) / mix["rate"] for i in range(n)]
    gaps = _permuted(gaps, seed, 4)
    pl = _permuted(quantiles(mix["prompt_len"], n), seed, 5)
    mt = _permuted(quantiles(mix["max_tokens"], n), seed, 6)
    out, t = [], 0.0
    for i in range(n):
        t += gaps[i]
        if t >= seconds:
            break
        out.append({"at": t, "prompt_len": pl[i], "max_tokens": mt[i]})
    return out
