"""The comparison that decides a run's ``correct``.

Once the window has closed and the server is freed, a sample of the
finished requests, drawn from the seed with the longest among them, is
run through the plain reference (the ``logits`` of the configuration's
model module, ``bench/models/``) over each prompt and its served
tokens. Every served token is greedy, so the reference's best
logit at its position should be that token's, to within rounding. The
number compared is the widest gap by which a served token's reference
logit lies below the reference's best (``max_logit_gap``); its limit is
the configuration's ``limits.max_logit_gap``, set between the program's
readings over a dozen seeds and the fp8 control's (``PERF.md``).

``check(..., control=True)`` reads the control through the same
comparison: at each position of the same prompts and served tokens it
compares the token that the reference computed in fp8 puts first
(``logits(control=True)``) in place of the served one.
"""

from __future__ import annotations

import random

import numpy as np

from bench import stats, traffic
from bench.reference import model as R


def pick_sample(records: list, seed: int, tokens: int) -> list:
    """The finished request with the most served tokens, then others in
    an order drawn from the seed, until ``tokens`` served tokens."""
    done = [r for r in records if stats.ok(r)]
    if not done:
        return []
    done.sort(key=lambda r: (-len(r["tokens"]), -r["prompt_len"],
                             r["stream"], r["index"]))
    sample, rest = [done[0]], done[1:]
    random.Random(seed).shuffle(rest)
    n = len(done[0]["tokens"])
    for r in rest:
        if n >= tokens:
            break
        sample.append(r)
        n += len(r["tokens"])
    return sample


def pass_length(conf: dict, mix: dict) -> int:
    """The one sequence length every reference pass of a cell uses."""
    arch = conf["arch"]
    b = arch["lop_block"]
    n = traffic.max_prompt(mix) + traffic.max_tokens(mix)
    return -(-n // b) * b


def gaps(conf: dict, mix: dict, seed: int, rec: dict, model,
         control=False):
    """(reference logits, served tokens) of one finished request, by the
    configuration's model module ``model``; with ``control`` the fp8
    control's logits in place of the reference's."""
    arch = conf["arch"]
    cap = R.capacity_of(arch, conf["server"]["max_len"])
    prompt = traffic.prompt(seed, rec["stream"], rec["index"],
                            rec["prompt_len"], arch["vocab"])
    toks = np.asarray(rec["tokens"], np.int64)
    seq = prompt + [int(t) for t in toks[:-1]]
    lg = model.logits(arch, seed, seq, len(prompt),
                      length=pass_length(conf, mix),
                      k_keep=R.k_keep_blocks(arch, cap),
                      n_rows=traffic.max_tokens(mix), control=control)
    return lg[:len(toks)], toks


def token_gaps(logits: np.ndarray, toks: np.ndarray) -> np.ndarray:
    return logits.max(-1) - logits[np.arange(len(toks)), toks]


def gap_stats(g: np.ndarray) -> dict:
    """The numbers a set of per-token gaps can be compared by: the
    widest gap, the mean gap, and the share of tokens that are not the
    reference's argmax."""
    if not len(g):
        return {"max_logit_gap": 0.0, "mean_logit_gap": 0.0,
                "argmax_disagreement": 0.0}
    return {"max_logit_gap": float(g.max()),
            "mean_logit_gap": float(g.mean()),
            "argmax_disagreement": float((g > 0).mean())}


def sample_gaps(conf: dict, mix: dict, seed: int, rec: dict, model,
                control: bool) -> np.ndarray:
    """Per-token gaps of one request: of its served tokens, or with
    ``control`` of the fp8 control's first choices."""
    lg, toks = gaps(conf, mix, seed, rec, model)
    if control:
        toks = gaps(conf, mix, seed, rec, model, control=True)[0].argmax(-1)
    return token_gaps(lg, toks)


def check(conf: dict, mix: dict, run: dict, seed: int, *, model,
          sample_tokens: int, control: bool = False) -> dict:
    """→ {"ok", "compared": {name: {"value", "limit"}}, "read": the
    gap statistics, "lines"}, by the configuration's model module
    ``model``. The configuration's ``limits`` name the numbers
    compared."""
    recs = stats.due_in_window(run)
    failed = sum(not stats.ok(r) for r in recs)
    sample = pick_sample(recs, seed, sample_tokens)
    g = np.concatenate([np.zeros(0)] + [
        sample_gaps(conf, mix, seed, r, model, control) for r in sample])
    read = gap_stats(g)
    compared = {name: {"value": read[name], "limit": float(limit)}
                for name, limit in conf["limits"].items()}
    compared["requests_failed"] = {"value": failed, "limit": 0}
    ok = len(g) > 0 and all(c["value"] <= c["limit"]
                            for c in compared.values())
    what = "fp8 control's tokens" if control else "served tokens"
    lines = [f"check: {len(g)} {what} of {len(sample)} of "
             f"{len(recs)} requests compared with the plain reference; "
             + ", ".join(f"{k} {v!r}" for k, v in read.items())]
    lines += [f"check: {name} {c['value']!r} limit {c['limit']!r}"
              for name, c in compared.items()]
    return {"ok": ok, "compared": compared, "read": read, "lines": lines}
