"""Continuous-batching serving driver over the typed serving API.

    PYTHONPATH=src python -m repro.launch.serve --arch bitnet-3b --reduced \
        --slots 4 --requests 8 --min-prompt 8 --max-prompt 48 --gen 16

Synthesizes a stream of requests with *staggered arrivals* and *variable
prompt lengths*, drives the :class:`repro.serving.scheduler.Scheduler`
through the :class:`repro.serving.api.InferenceEngine` protocol
(admit → prefill → insert → decode → evict per lane), and reports
per-request latency percentiles — TTFT, end-to-end AND inter-token
latency (ITL p50/p99 over every decode gap) — alongside aggregate
tokens/s and the modeled LOP KV-traffic reduction.

Sampling is per-request (:class:`repro.serving.api.SamplingParams`):
``--temperature/--top-k/--top-p`` apply to every synthetic request (each
gets its own seed), the default being greedy. ``--verify`` replays every
request alone through the lockstep reference path *with the same
sampling params* and checks the continuous-batching run emitted
identical tokens — bitwise for greedy, same-seed identical for sampled.
``--stream`` prints tokens as each lane emits them (the ``on_token``
streaming callback).

Chunked prefill (DESIGN.md §Chunked-prefill) is ON by default when the
engine declares ``supports_chunked``: each serve cycle advances one
fixed-shape prefill chunk AND one decode step, so TTFT is measured
*under interleaving*. ``--no-chunked`` restores run-to-completion
prefill (the ablation baseline); ``--chunk-tokens`` overrides the chunk
size (default: the arch's ``lop_block``).

Self-speculative decoding (DESIGN.md §Speculative-decoding) is opt-in:
``--spec-decode --gamma 4`` drafts γ tokens per lane with a degraded-cost
pass (``--draft-layers`` of the stack, LOP selection pinched to
``--draft-k`` blocks) and verifies all γ+1 positions exactly in ONE
prefill-chunk launch, emitting the agreeing prefix plus the verifier's
bonus token; the report adds accept rate, tokens per verify launch, and
full-model launches per generated token. Greedy speculative runs emit
the plain-decode token stream (``--verify`` still holds).

Prefix caching (DESIGN.md §Prefix-caching) is likewise ON by default
under chunked prefill: ``--shared-prefix-tokens N --prefix-reuse-frac F``
synthesizes a trace where a fraction of requests share one N-token
prompt prefix (a system prompt / few-shot template); the scheduler
prefills it once and clones it into every later sharer, and the report
splits TTFT by prefix hit vs miss plus prefill tokens computed vs
served. ``--no-prefix-cache`` is the cold baseline.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.core.lop import kv_traffic_bytes
from repro.launch import compile_cache
from repro.launch.train import resolve_config
from repro.serving import metrics as smetrics
from repro.serving.api import GenerateRequest, SamplingParams, StepResult
from repro.serving.quantize import build_serving_params
from repro.serving.scheduler import Scheduler, lockstep_generate


def make_requests(cfg, *, n_requests: int, min_prompt: int, max_prompt: int,
                  gen: int, seed: int = 0,
                  sampling: SamplingParams | None = None,
                  shared_prefix_tokens: int = 0,
                  prefix_reuse_frac: float = 1.0,
                  deadline_ms: float | None = None,
                  on_token=None):
    """Synthetic traffic: variable prompt lengths, FIFO arrival order.
    With ``sampling`` given, request ``rid`` gets its params under seed
    ``sampling.seed + rid`` (distinct per-request streams).

    ``shared_prefix_tokens > 0`` models a shared system prompt / few-shot
    template: the first ``round(prefix_reuse_frac * n_requests)`` requests
    prepend ONE common ``shared_prefix_tokens``-token prefix to their
    (still per-request random) suffix; the rest stay fully cold. Prompt
    lengths become ``shared_prefix_tokens + [min_prompt, max_prompt]``
    for sharers."""
    if n_requests < 1:
        raise ValueError(f"--requests must be >= 1, got {n_requests}")
    if not 0 < min_prompt <= max_prompt:
        raise ValueError(f"need 0 < --min-prompt <= --max-prompt, got "
                         f"{min_prompt}..{max_prompt}")
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab,
                          (shared_prefix_tokens,)).astype(np.int32)
    n_sharers = round(prefix_reuse_frac * n_requests) \
        if shared_prefix_tokens else 0
    reqs = []
    for rid in range(n_requests):
        plen = int(rng.integers(min_prompt, max_prompt + 1))
        prompt = rng.integers(0, cfg.vocab, (plen,)).astype(np.int32)
        if rid < n_sharers:
            prompt = np.concatenate([shared, prompt])
        frames = patches = None
        if cfg.family == "encdec":
            frames = (rng.standard_normal((4 * plen, cfg.d_model))
                      .astype(np.float32) * 0.02)
        if cfg.family == "vlm":
            patches = (rng.standard_normal((cfg.n_img_tokens, cfg.d_model))
                       .astype(np.float32) * 0.02)
        sp = SamplingParams() if sampling is None else \
            dataclasses.replace(sampling, seed=sampling.seed + rid)
        reqs.append(GenerateRequest(
            rid=rid, prompt=prompt, max_new_tokens=gen, sampling=sp,
            deadline_ms=deadline_ms, on_token=on_token, frames=frames,
            patches=patches))
    return reqs


def serve_loop(cfg, *, n_slots: int = 4, n_requests: int = 8,
               min_prompt: int = 8, max_prompt: int = 48, gen: int = 16,
               arrival_period: float = 0.0, seed: int = 0,
               use_lop: bool = True, verify: bool = False,
               chunked: bool | None = None,
               chunk_tokens: int | None = None,
               prefix_cache: bool | None = None,
               shared_prefix_tokens: int = 0,
               prefix_reuse_frac: float = 1.0,
               spec_decode: bool = False, gamma: int = 4,
               draft_layers: int | None = None,
               draft_k: int | None = None,
               sampling: SamplingParams | None = None,
               max_queue: int | None = None,
               deadline_ms: float | None = None,
               on_token=None, engine=None):
    """Continuous-batching run over staggered arrivals. → stats dict.

    ``arrival_period`` (seconds) spaces request arrivals; requests that
    have not arrived yet stay out of the queue, so lanes drain and refill
    mid-run exactly as a live server would. 0 = all arrive at t0 (arrival
    order still staggers admissions once lanes fill).

    ``shared_prefix_tokens``/``prefix_reuse_frac`` shape the trace (see
    :func:`make_requests`); ``prefix_cache`` gates the scheduler's prefix
    store (None = on when chunked). TTFT is reported split by prefix
    hit/miss. An injected ``engine`` is reused across calls (shared jit
    caches — the benchmark's cache-on vs cache-off arms).

    Robustness knobs (DESIGN.md §Fault-tolerance): ``max_queue`` bounds
    the admit queue — submits past the bound answer immediately with
    reason ``"shed"``; ``deadline_ms`` stamps every synthetic request
    with that latency budget, enforced at admit, between prefill chunks
    and per decode sweep (reason ``"deadline"``). The stats dict carries
    the scheduler's robustness telemetry (shed/deadline/fault counters,
    queue-depth peak, prefix checksum failures, watchdog trips).
    ``--verify`` checks token equivalence only for requests that ran to
    a natural finish — shed/deadline/cancelled/fault requests and lanes
    a fault recovery touched have no lockstep counterpart."""
    if engine is not None:
        cfg, qp = engine.cfg, engine.qp
    else:
        qp = build_serving_params(cfg, jax.random.PRNGKey(seed))
    reqs = make_requests(cfg, n_requests=n_requests, min_prompt=min_prompt,
                         max_prompt=max_prompt, gen=gen, seed=seed + 1,
                         sampling=sampling,
                         shared_prefix_tokens=shared_prefix_tokens,
                         prefix_reuse_frac=prefix_reuse_frac,
                         deadline_ms=deadline_ms, on_token=on_token)
    max_len = max_prompt + gen + shared_prefix_tokens
    if cfg.family == "vlm":
        max_len += cfg.n_img_tokens       # image prefix shares the cache
    # fresh per-run registry: the same metric families the HTTP server
    # exports from /metrics, so a driver run and a live server are
    # diffable dashboards (DESIGN.md §Serving-metrics)
    registry = smetrics.MetricsRegistry()
    sched = Scheduler(cfg, qp, n_slots=n_slots, max_len=max_len,
                      use_lop=use_lop, chunked=chunked,
                      chunk_tokens=None if engine is not None
                      else chunk_tokens,
                      prefix_cache=prefix_cache,
                      spec_decode=spec_decode, gamma=gamma,
                      draft_layers=None if engine is not None
                      else draft_layers,
                      draft_k=None if engine is not None else draft_k,
                      max_queue=max_queue, engine=engine,
                      metrics=registry)

    t0 = time.monotonic()
    pending = list(reqs)
    n_steps = 0
    while pending or sched.has_work():
        now = time.monotonic() - t0
        while pending and now >= pending[0].rid * arrival_period:
            req = pending.pop(0)
            sched.submit(dataclasses.replace(req,
                                             arrival=time.monotonic()))
            now = time.monotonic() - t0
        sched.admit()
        if sched.n_active or sched.n_prefilling:
            sched.step()
            n_steps += 1
        elif pending:
            # idle until the next arrival
            time.sleep(max(0.0,
                           pending[0].rid * arrival_period - now))
    wall = time.monotonic() - t0

    results = sorted(sched.results, key=lambda r: r.rid)
    total_toks = sum(len(r.tokens) for r in results)
    lat = [r.latency for r in results]
    ttft = [r.ttft for r in results]
    ttft_hit = [r.ttft for r in results if r.cached_len] or [np.nan]
    ttft_miss = [r.ttft for r in results if not r.cached_len] or [np.nan]
    itl = [g for r in results for g in r.itl] or [0.0]
    out = {
        "results": results,
        "tokens": {r.rid: np.asarray(r.tokens, np.int32) for r in results},
        "wall_s": wall,
        "decode_steps": n_steps,
        "tokens_per_s": total_toks / max(wall, 1e-9),
        "metrics": registry,
        **smetrics.summarize(lat, (50, 90, 99), prefix="latency_"),
        **smetrics.summarize(ttft, (50, 90, 99), prefix="ttft_"),
        **smetrics.summarize(itl, (50, 99), prefix="itl_"),
        **smetrics.summarize(ttft_hit, (50, 99), prefix="ttft_hit_"),
        **smetrics.summarize(ttft_miss, (50, 99), prefix="ttft_miss_"),
        "prefill_compiles": sched.prefill_compiles,
        "chunked": sched.chunked,
        "interleaved_decode_steps": sched.interleaved_decode_steps,
        "full_prefill_stalls": sched.full_prefill_stalls,
        "prefix_cache": sched.prefix_store is not None,
        "prefix_hits": sched.prefix_hits,
        "prefix_hit_tokens": sched.prefix_hit_tokens,
        "prefill_tokens_computed": sched.prefill_tokens_computed,
        "prefill_tokens_served": sched.prefill_tokens_served,
        "spec_decode": sched.spec,
        "spec_rounds": sched.spec_rounds,
        "spec_drafted": sched.spec_drafted,
        "spec_accepted": sched.spec_accepted,
        "spec_emitted": sched.spec_emitted,
        "spec_verify_launches": sched.spec_verify_launches,
        "draft_launches": sched.draft_launches,
        "decode_launches": sched.decode_launches,
        # draft acceptance rate and decode amortization: full-model
        # launches (plain decode + verify) per token actually generated —
        # < 1.0 is the speculative win
        "spec_accept_rate": (sched.spec_accepted
                             / max(1, sched.spec_drafted)),
        "spec_tokens_per_verify": (sched.spec_emitted
                                   / max(1, sched.spec_verify_launches)),
        "full_launches_per_token": ((sched.decode_launches
                                     + sched.spec_verify_launches)
                                    / max(1, total_toks)),
        # robustness telemetry (DESIGN.md §Fault-tolerance), read back
        # off the metrics registry — the same counters /metrics exports
        "max_queue": max_queue,
        "shed_count": int(registry.value("repro_requests_shed_total")),
        "queue_depth_peak": sched.queue_depth_peak,
        "deadline_ms": deadline_ms,
        "deadline_count": int(
            registry.value("repro_deadline_expired_total")),
        "fault_events": int(registry.value("repro_fault_events_total")),
        "fault_recoveries": int(
            registry.value("repro_fault_recoveries_total")),
        "fault_finishes": int(
            registry.value("repro_fault_finishes_total")),
        "prefix_lookup_failures": sched.prefix_lookup_failures,
        "checksum_failures": (sched.prefix_store.checksum_failures
                              if sched.prefix_store is not None else 0),
        "spec_watchdog_trips": sched.spec_watchdog_trips,
    }

    if verify:
        # only naturally-finished requests have a lockstep counterpart:
        # a shed/deadline/cancelled/fault request was cut off mid-stream,
        # and a lane a fault recovery touched is exact only by the
        # recovery contract, which the chaos test checks separately
        reason = {r.rid: r.finish_reason for r in results}
        mismatches, skipped = [], []
        for req in reqs:
            if reason.get(req.rid) not in ("eos", "stop", "length") \
                    or req.rid in sched.fault_rids:
                skipped.append(req.rid)
                continue
            ref = lockstep_generate(cfg, qp, req.prompt, req.max_new_tokens,
                                    max_len=max_len, use_lop=use_lop,
                                    frames=req.frames, patches=req.patches,
                                    sampling=req.sampling, engine=engine)
            if list(out["tokens"][req.rid]) != ref:
                mismatches.append(req.rid)
        out["verified"] = not mismatches
        out["mismatched_rids"] = mismatches
        out["verify_skipped_rids"] = skipped
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="bitnet-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--min-prompt", type=int, default=8)
    ap.add_argument("--max-prompt", type=int, default=48)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--arrival-period", type=float, default=0.0,
                    help="seconds between request arrivals (staggered)")
    ap.add_argument("--no-lop", action="store_true")
    ap.add_argument("--no-chunked", action="store_true",
                    help="run-to-completion prefill (disable chunked "
                         "prefill/decode interleaving)")
    ap.add_argument("--chunk-tokens", type=int, default=None,
                    help="prefill chunk size (default: arch lop_block)")
    ap.add_argument("--shared-prefix-tokens", type=int, default=0,
                    help="length of ONE common prompt prefix (a shared "
                         "system prompt) prepended to sharing requests")
    ap.add_argument("--prefix-reuse-frac", type=float, default=1.0,
                    help="fraction of requests sharing the common prefix")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable the scheduler's prefix store (every "
                         "prompt prefills cold)")
    ap.add_argument("--spec-decode", action="store_true",
                    help="self-speculative decoding: draft cheap tokens "
                         "(truncated layer stack + degraded LOP budget), "
                         "verify γ+1 positions in one prefill-chunk "
                         "launch, accept the agreeing prefix")
    ap.add_argument("--gamma", type=int, default=4,
                    help="speculative draft length per verify launch")
    ap.add_argument("--draft-layers", type=int, default=None,
                    help="decoder layers the draft pass runs (default: "
                         "n_layers // 2)")
    ap.add_argument("--draft-k", type=int, default=None,
                    help="LOP blocks the draft attention keeps "
                         "(default: 1)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="per-request top-k filter (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="per-request nucleus filter (1 = off)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="base PRNG seed; request rid samples under "
                         "seed+rid")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="admission bound: submits past this queue depth "
                         "are load-shed (reason \"shed\") instead of "
                         "queued unboundedly")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request latency budget from arrival; "
                         "expired requests retire with reason "
                         "\"deadline\" at admit, between prefill chunks "
                         "or mid-decode")
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as lanes emit them (on_token "
                         "streaming callback)")
    ap.add_argument("--verify", action="store_true",
                    help="replay each request alone (lockstep, same "
                         "SamplingParams) and check token-exact agreement")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = resolve_config(args.arch, args.reduced)
    sampling = SamplingParams(temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p,
                              seed=args.sample_seed)
    mode_s = "greedy" if sampling.greedy else (
        f"T={sampling.temperature} top_k={sampling.top_k} "
        f"top_p={sampling.top_p}")
    print(f"serving {cfg.name}: {args.slots} slots, {args.requests} requests"
          f" (prompts {args.min_prompt}-{args.max_prompt}, gen {args.gen}),"
          f" lop={'off' if args.no_lop else 'on'}, sampling {mode_s}")

    on_token = None
    if args.stream:
        def on_token(sr: StepResult):
            flag = f" <{sr.finish_reason}>" if sr.finished else ""
            print(f"  [rid {sr.rid}] #{sr.index} -> {sr.token}{flag}")

    out = serve_loop(cfg, n_slots=args.slots, n_requests=args.requests,
                     min_prompt=args.min_prompt, max_prompt=args.max_prompt,
                     gen=args.gen, arrival_period=args.arrival_period,
                     use_lop=not args.no_lop, verify=args.verify,
                     chunked=not args.no_chunked,
                     chunk_tokens=args.chunk_tokens,
                     prefix_cache=not args.no_prefix_cache,
                     shared_prefix_tokens=args.shared_prefix_tokens,
                     prefix_reuse_frac=args.prefix_reuse_frac,
                     spec_decode=args.spec_decode, gamma=args.gamma,
                     draft_layers=args.draft_layers, draft_k=args.draft_k,
                     sampling=None if sampling.greedy else sampling,
                     max_queue=args.max_queue, deadline_ms=args.deadline_ms,
                     on_token=on_token)

    print(f"{'rid':>4} {'plen':>5} {'hit':>5} {'toks':>5} {'ttft_ms':>8} "
          f"{'latency_ms':>10}  finish")
    for r in out["results"]:
        print(f"{r.rid:>4} {r.prompt_len:>5} {r.cached_len:>5} "
              f"{len(r.tokens):>5} "
              f"{r.ttft * 1e3:>8.1f} {r.latency * 1e3:>10.1f}  "
              f"{r.finish_reason}")
    mode = ("chunked prefill (interleaved; "
            f"{out['interleaved_decode_steps']} decode steps taken while "
            "a prompt was mid-prefill)" if out["chunked"] else
            f"run-to-completion prefill ({out['full_prefill_stalls']} "
            "full-batch stalls)")
    print(f"wall {out['wall_s']:.2f}s, {out['decode_steps']} serve cycles, "
          f"{out['tokens_per_s']:.1f} tok/s, "
          f"{out['prefill_compiles']} prefill compiles, {mode}")
    print(f"latency p50/p90/p99: {out['latency_p50'] * 1e3:.1f} / "
          f"{out['latency_p90'] * 1e3:.1f} / "
          f"{out['latency_p99'] * 1e3:.1f} ms; "
          f"ttft p50/p90: {out['ttft_p50'] * 1e3:.1f} / "
          f"{out['ttft_p90'] * 1e3:.1f} ms; "
          f"itl p50/p99: {out['itl_p50'] * 1e3:.1f} / "
          f"{out['itl_p99'] * 1e3:.1f} ms")
    if out["spec_decode"]:
        print(f"speculative decode: {out['spec_rounds']} rounds, "
              f"accept rate {out['spec_accept_rate']:.2f} "
              f"({out['spec_accepted']}/{out['spec_drafted']} drafts), "
              f"{out['spec_tokens_per_verify']:.2f} tokens/verify launch, "
              f"{out['full_launches_per_token']:.2f} full-model launches "
              f"per token ({out['decode_launches']} decode + "
              f"{out['spec_verify_launches']} verify)")
    if out["prefix_cache"]:
        print(f"prefix cache: {out['prefix_hits']} hits "
              f"({out['prefix_hit_tokens']} tokens served from interned "
              f"pages), prefill tokens computed/served: "
              f"{out['prefill_tokens_computed']}/"
              f"{out['prefill_tokens_served']}; "
              f"ttft p50 hit/miss: {out['ttft_hit_p50'] * 1e3:.1f} / "
              f"{out['ttft_miss_p50'] * 1e3:.1f} ms")
    if args.max_queue is not None or args.deadline_ms is not None \
            or out["shed_count"] or out["deadline_count"] \
            or out["fault_events"]:
        n_req = len(out["results"])
        print(f"robustness: queue peak {out['queue_depth_peak']}"
              f"{f' (bound {args.max_queue})' if args.max_queue else ''}, "
              f"{out['shed_count']} shed, "
              f"{out['deadline_count']} deadline-expired "
              f"(deadline-hit ratio "
              f"{1.0 - out['deadline_count'] / max(1, n_req):.2f}), "
              f"{out['fault_events']} fault events "
              f"({out['fault_recoveries']} recovered, "
              f"{out['fault_finishes']} gave up), "
              f"{out['prefix_lookup_failures']} prefix-lookup failures, "
              f"{out['checksum_failures']} checksum failures, "
              f"{out['spec_watchdog_trips']} spec-watchdog trips")
    if args.verify:
        status = "OK" if out["verified"] else \
            f"MISMATCH rids={out['mismatched_rids']}"
        if out.get("verify_skipped_rids"):
            status += (f" ({len(out['verify_skipped_rids'])} requests "
                       "skipped: no natural finish)")
        print(f"continuous-batching vs lockstep token equivalence: {status}")

    m = args.max_prompt + args.gen
    full = kv_traffic_bytes(m, cfg.hd, m, with_lop=False)
    lop = kv_traffic_bytes(m, cfg.hd, int(m * cfg.lop_keep), with_lop=True)
    print(f"modeled KV traffic/head/query: {full} B dense → {lop} B with LOP"
          f" ({full / lop:.1f}× reduction at keep={cfg.lop_keep})")


if __name__ == "__main__":
    main()
