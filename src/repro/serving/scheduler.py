"""Continuous-batching scheduler over the typed engine protocol.

The serving layer's control plane: a FIFO request queue feeding ``n_slots``
persistent decode lanes. The scheduler speaks ONLY the
:class:`repro.serving.api.InferenceEngine` protocol — every
family-specific behaviour (chunked vs run-to-completion prefill, exact
vs pow2-bucketed compile lengths, image prefixes) is a *capability the
engine declares*, not a name the scheduler checks (DESIGN.md
§Serving-API). The lifecycle per request is

    admit → prefill → insert → decode → evict

  admit    — a queued request is taken once a lane is free; the other lanes
             keep decoding in the meantime. Cancelled requests are dropped
             before they ever touch a lane. With the prefix cache on
             (chunked engines, default), the prompt is matched against
             the :class:`repro.serving.cache.PrefixStore` hash chain:
             hits clone the cached pages into their reserved lanes —
             grouped per prefix node, ONE ``engine.bulk_insert`` scatter
             per group — and plan chunks for the uncached suffix only,
             so a shared prompt costs one prefill plus per-request
             suffixes (DESIGN.md §Prefix-caching).
  prefill  — two regimes (DESIGN.md §Chunked-prefill), selected by
             ``engine.supports_chunked``:

             *chunked*: the prompt is split into fixed-size token chunks
             (``engine.chunk_tokens``) and ONE chunk is advanced per
             ``step()``, interleaved with the running decode batch.
             Each chunk round-trips ``engine.prefill_chunk`` (extract →
             forward → partial insert, ``active=False``) so the
             in-flight K/V lives in the reserved lane; the final chunk
             activates it and seeds the first token through the sampler.
             A prefix-hit lane starts its chunk grid at the cached block
             boundary (``start = cached_len`` — the same bitwise
             ``(start, kv_len)`` carry every later chunk uses), and a
             finished prompt's block-aligned pages are interned back
             into the store at activation.

             *run-to-completion*: the request runs alone (batch 1)
             through ``engine.prefill``. Engines declaring
             ``exact_length_prefill`` (recurrent state, MoE routers,
             encoder-tied compiles) get exact-length compiles; others
             get pow2 buckets.
  insert   — the batch-1 cache is written into the lane
             (``engine.insert``).
  decode   — ONE ``engine.decode_step`` advances *all* active lanes and
             samples their next tokens in the same dispatch
             (:mod:`repro.serving.sampling`: greedy argmax fast path,
             per-lane temperature/top-k/top-p with lane-local PRNG
             keys). Tokens stream to each request's ``on_token``
             callback as they are emitted.
  evict    — on EOS, a stop-sequence hit, the token budget, or
             cancellation the lane is retired (``engine.evict``) and
             immediately reusable.

Determinism note: lanes are independent through every attention/FFN path
and the sampler's key schedule is lane-local
(:mod:`repro.serving.sampling`), so a request decodes the same tokens
whether it shares the pool, prefills in chunks, or runs alone
(:func:`lockstep_generate`, the batch-1 reference implementation of the
same protocol) — greedy bitwise, sampled same-seed identical; the
equivalence ``tests/test_serving_api.py`` pins down. The exception is
MoE capacity dropping, which ranks tokens across the batch; with a
generous ``capacity_factor`` the paths agree, but bit-exactness is only
guaranteed for dense/vlm/recurrent families.
"""

from __future__ import annotations

import os
import time
from collections import deque
from dataclasses import dataclass, replace

import jax.numpy as jnp
import numpy as np

from repro.serving import metrics as _metrics
from repro.serving.api import (GREEDY, ExistingPrefix, FinishedRequest,
                               GenerateRequest, PooledEngine, SamplingParams,
                               StepResult)
from repro.serving.cache import PrefixStore, pool_capacity
from repro.serving.faults import PrefixLookupError
from repro.serving.metrics import span

# Back-compat names — the typed API in repro.serving.api is the source of
# truth; the old scheduler-local dataclasses are these aliases now.
Request = GenerateRequest
RequestResult = FinishedRequest


@dataclass
class _Lane:
    """Host-side state of one occupied decode lane."""
    req: GenerateRequest
    tokens: list                       # emitted tokens, in order
    remaining: int                     # budget left after `tokens`
    t_admit: float
    t_first: float
    token_times: list                  # clock() stamp per emitted token
    cached_len: int = 0                # prompt tokens cloned from the store
    no_spec: bool = False              # drafting disabled (watchdog/fault)
    zero_accept_rounds: int = 0        # consecutive 0-accept spec rounds


@dataclass
class _Prefill:
    """Host-side state of one lane mid-way through chunked prefill."""
    slot: int
    req: GenerateRequest
    chunks: list                       # [1, C_k] int32 token chunks
    starts: list                       # global stream position of chunk k
    seq_ends: list                     # true end written after chunk k
    t_admit: float
    next_chunk: int = 0
    cached_len: int = 0                # prefix tokens the lane resumes past


def pow2_bucket(n: int, *, lo: int = 16, hi: int | None = None) -> int:
    """Smallest power-of-two ≥ n (clamped to [lo, hi]) — the prefill
    compilation bucket of the run-to-completion path. A few buckets cover
    every prompt length, bounding recompiles regardless of traffic mix."""
    b = lo
    while b < n:
        b *= 2
    return min(b, hi) if hi is not None else b


class Scheduler:
    """Continuous-batching front-end over an :class:`InferenceEngine`.

    Drives the admit → prefill → insert → decode → evict lifecycle over a
    slot-paged pool. ``step()`` advances ONE prefill chunk of the oldest
    mid-prefill lane (chunked regime), then every active decode lane one
    sampled token, and returns the requests that completed; ``admit()``
    fills free lanes from the queue. The driver (``launch/serve.py``)
    interleaves the two.

    ``engine`` may be any protocol implementation; by default a
    :class:`repro.serving.api.PooledEngine` is built from ``(cfg, qp)``.
    ``chunked=None`` (default) enables chunked prefill when the engine
    declares ``supports_chunked``; ``False`` forces run-to-completion
    prefill everywhere (the ablation baseline in
    ``benchmarks/prefill_interleave.py``).

    ``prefix_cache=None`` (default) enables prefix caching when chunked
    prefill is on and the engine declares a ``prefix_block``; ``False``
    disables it (the cache-off arm of ``benchmarks/prefix_cache.py``).
    ``prefix_cache_tokens`` bounds the store's interned pages (default
    4× the pool's token capacity — prefix pages trade against slot-pool
    pressure, not unboundedly). Matching is skipped for requests with an
    image prefix (patch embeddings shift every text position, so token
    chains would alias distinct streams).

    Fault tolerance (DESIGN.md §Fault-tolerance): ``max_queue`` bounds
    the admit queue — a submit past the bound is load-shed immediately
    (reason ``"shed"``, reject-newest) instead of growing the queue
    without bound; ``None`` keeps the legacy unbounded FIFO. Requests
    carrying ``deadline_ms`` are retired with reason ``"deadline"`` at
    admit, between prefill chunks and per decode sweep. A lane whose
    decode logits go non-finite is quarantined, rewound bitwise
    (``engine.rollback``) and retried once through the engine's no-LOP
    recovery step — reason ``"fault"`` only if the retry fails too.
    ``spec_watchdog`` disables drafting for a lane after that many
    consecutive zero-accept speculative rounds. With ``REPRO_PARANOID=1``
    in the environment, :meth:`check_invariants` runs after every step.
    """

    def __init__(self, cfg, qp, *, n_slots: int, max_len: int,
                 use_lop: bool = True, bucket_min: int = 16,
                 chunked: bool | None = None, chunk_tokens: int | None = None,
                 prefix_cache: bool | None = None,
                 prefix_cache_tokens: int | None = None,
                 spec_decode: bool = False, gamma: int = 4,
                 draft_layers: int | None = None, draft_k: int | None = None,
                 max_queue: int | None = None, spec_watchdog: int = 3,
                 clock=time.monotonic, engine=None, metrics=None):
        if engine is not None:
            # an injected engine owns its own configuration — reject
            # overrides that would otherwise be silently ignored
            assert chunk_tokens is None, \
                "pass chunk_tokens to the engine, not the Scheduler, " \
                "when injecting one"
            assert draft_layers is None and draft_k is None, \
                "pass draft_layers/draft_k to the engine, not the " \
                "Scheduler, when injecting one"
            use_lop = getattr(engine, "use_lop", use_lop)
        self.engine = engine if engine is not None else PooledEngine(
            cfg, qp, max_len=max_len, use_lop=use_lop,
            chunk_tokens=chunk_tokens, draft_layers=draft_layers,
            draft_k=draft_k)
        self.cfg = getattr(self.engine, "cfg", cfg)
        self.n_slots = n_slots
        self.max_len = max_len
        self.use_lop = use_lop
        self.bucket_min = bucket_min
        self.clock = clock
        self.pool = self.engine.init_pool(n_slots)
        self.capacity = pool_capacity(self.pool)
        # cross-attention lanes have their own (cross_ctx) capacity
        self.cross_capacity = (self.pool["cross"]["k"].shape[3]
                               if "cross" in self.pool else 0)
        self.chunked = ((chunked is None or chunked)
                        and self.engine.supports_chunked)
        self.chunk_tokens = self.engine.chunk_tokens
        # speculative decoding rides the engine's declared capability —
        # an engine without rewindable positional state (or a chunked
        # verify path) silently degrades to plain decode
        if spec_decode:
            assert gamma >= 1, f"spec_decode needs gamma >= 1, got {gamma}"
        self.spec = bool(spec_decode) and getattr(
            self.engine, "supports_speculative", False)
        self.gamma = gamma
        self.prefix_store: PrefixStore | None = None
        if self.chunked and getattr(self.engine, "prefix_block", 0) \
                and (prefix_cache is None or prefix_cache):
            self.prefix_store = PrefixStore(
                self.engine.prefix_block,
                max_tokens=(prefix_cache_tokens
                            if prefix_cache_tokens is not None
                            else 4 * self.capacity))

        self.queue: deque[GenerateRequest] = deque()
        self.lanes: list[_Lane | None] = [None] * n_slots
        self._free: deque[int] = deque(range(n_slots))
        self._prefilling: deque[_Prefill] = deque()
        # pending next-token per lane, fed to the next decode step
        self._next_tok = np.zeros((n_slots, 1), np.int32)
        self.results: list[FinishedRequest] = []
        # interleaving telemetry (benchmarks/prefill_interleave.py):
        # decode steps taken while some prompt was mid-prefill, and
        # whole-prompt prefills that ran while decode lanes sat idle
        self.interleaved_decode_steps = 0
        self.full_prefill_stalls = 0
        # prefix-cache telemetry (benchmarks/prefix_cache.py): hit counts,
        # prompt tokens served from interned pages vs actually computed
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0
        self.prefill_tokens_computed = 0
        self.prefill_tokens_served = 0
        # speculative-decoding telemetry (benchmarks/spec_decode.py):
        # full-model launches = decode_launches + spec_verify_launches;
        # draft_launches are the degraded-cost proposer steps
        self.spec_rounds = 0
        self.spec_drafted = 0          # draft tokens proposed
        self.spec_accepted = 0         # drafts that matched verify
        self.spec_emitted = 0          # tokens emitted by spec rounds
        self.spec_verify_launches = 0
        self.draft_launches = 0
        self.decode_launches = 0       # plain (non-spec) decode steps
        # fault-tolerance knobs + telemetry (DESIGN.md §Fault-tolerance)
        self.max_queue = max_queue
        self.spec_watchdog = spec_watchdog
        self.shed_count = 0            # submits rejected at the bound
        self.queue_depth_peak = 0
        self.deadline_count = 0        # requests retired past deadline
        self.fault_events = 0          # non-finite-logit detections
        self.fault_recoveries = 0      # rollback+retry that succeeded
        self.fault_finishes = 0        # lanes retired with reason "fault"
        self.fault_rids: set = set()   # rids a fault recovery touched
        self.prefix_lookup_failures = 0
        self.spec_watchdog_trips = 0
        self.paranoid = os.environ.get("REPRO_PARANOID") == "1"
        # structured telemetry (DESIGN.md §Serving-frontend): the same
        # events as the plain int attributes above, published onto a
        # metrics registry so the synthetic driver and the HTTP server
        # export identical series; per-request StageTimers record the
        # queue → prefill → decode spans under the scheduler's clock
        self.metrics = metrics if metrics is not None else _metrics.REGISTRY
        self._m = _metrics.scheduler_instruments(self.metrics)
        self._timers: dict = {}

    @property
    def prefill_compiles(self) -> int:
        return self.engine.prefill_compiles

    # ---------------- queue ----------------

    def submit(self, req: GenerateRequest) -> bool:
        # attention-free pools (capacity 0: recurrent state only) have no
        # token-capacity bound — only the prompt buffer limits them
        need = (len(req.prompt) + req.max_new_tokens
                + self.engine.prefix_len(req))
        assert not self.capacity or need <= self.capacity, (
            f"request {req.rid} needs {need} tokens but pool capacity is "
            f"{self.capacity}")
        if self.spec and self.capacity:
            # speculative rounds transiently write up to γ+1 rows past a
            # lane's committed length; `_spec_gamma` shrinks γ toward the
            # capacity boundary and a lane whose last row is the final
            # capacity position falls back to plain decode — that
            # fallback needs the lane's LAST committed write (position
            # need−1) in bounds, which is the bound above. Assert the
            # clamp's own precondition at admit so an off-by-γ overflow
            # fails loudly here, not as cache corruption mid-round.
            gam = req.sampling.gamma if req.sampling else 0
            assert gam >= 0, (
                f"request {req.rid}: sampling.gamma must be >= 0 "
                f"(0 = scheduler default), got {gam}")
        assert req.frames is None or len(req.frames) <= \
            self.cross_capacity, (
            f"request {req.rid} has {len(req.frames)} encoder frames but "
            f"the pool's cross capacity is {self.cross_capacity}")
        if req.arrival is None:
            req = replace(req, arrival=self.clock())
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            # load shedding, reject-newest: overload answers immediately
            # with reason "shed" instead of queueing unboundedly — the
            # queued requests keep their admission order and their
            # deadlines stay meetable
            self.shed_count += 1
            self._m.shed.inc()
            self._record_abort(req, reason="shed")
            return False
        timer = _metrics.StageTimer(self.clock)
        timer.enter("queue")
        self._timers[req.rid] = timer
        self.queue.append(req)
        self.queue_depth_peak = max(self.queue_depth_peak, len(self.queue))
        self._m.queue_depth.set(len(self.queue))
        return True

    @property
    def n_active(self) -> int:
        return sum(l is not None for l in self.lanes)

    @property
    def n_prefilling(self) -> int:
        return len(self._prefilling)

    def has_work(self) -> bool:
        return bool(self.queue) or bool(self._prefilling) \
            or self.n_active > 0

    # ---------------- admit / prefill / insert ----------------

    def _bucket(self, prompt_len: int) -> int:
        if self.engine.exact_length_prefill:
            return prompt_len
        return pow2_bucket(prompt_len, lo=self.bucket_min,
                           hi=self.max_len)

    def _plan_chunks(self, req: GenerateRequest, skip: int = 0):
        """Host-side chunk grid of one prompt (fixed C-token shapes).

        The final chunk is right-padded to the same C so every chunk of
        every prompt hits ONE compiled shape; ``seq_end`` keeps the pad
        out of ``lengths`` and the causal mask keeps it out of every real
        query row. Only when the padded end would spill past the pool
        capacity (a near-capacity prompt) does the tail fall back to its
        exact length.

        ``skip`` (a prefix-cache hit: a block-aligned count of prompt
        tokens already in the lane) plans chunks for the suffix
        ``[skip, plen)`` only — the first chunk starts at the cached
        boundary, the same traced ``(start, kv_len)`` carry every
        non-first chunk already uses, so the compiled chunk shape is
        unchanged.
        """
        plen = len(req.prompt)
        prefix = self.engine.prefix_len(req)
        c = self.chunk_tokens
        n = max(1, -(-(plen - skip) // c))
        chunks, starts, seq_ends = [], [], []
        for k in range(n):
            lo, hi = skip + k * c, min(plen, skip + k * c + c)
            width = c
            if self.capacity and prefix + lo + c > self.capacity:
                width = hi - lo                 # near-capacity exact tail
            buf = np.zeros((1, width), np.int32)
            buf[0, :hi - lo] = req.prompt[lo:hi]
            chunks.append(buf)
            starts.append(prefix + lo if (k or skip) else 0)
            seq_ends.append(prefix + hi)
        return chunks, starts, seq_ends

    def admit(self) -> int:
        """Admit queued requests into free lanes. Returns #admitted.

        Chunked regime: the lane is *reserved* and the prompt's chunk grid
        queued — no forward pass runs here; ``step()`` advances one chunk
        per cycle. Prompts matching the prefix store plan their uncached
        suffix only; the matched pages are cloned after the admit sweep,
        grouped per prefix node so N hits on one prefix cost ONE
        ``bulk_insert`` scatter. Run-to-completion regime: the whole
        prompt prefills synchronously (stalling any active decode lanes —
        counted in ``full_prefill_stalls``) and the lane activates
        immediately. Cancelled queue entries retire without touching a
        lane.
        """
        with span("repro.admit") as sp:
            n = self._admit()
            sp.set_metadata(admitted=n)
        return n

    def _admit(self) -> int:
        n = 0
        clones: dict = {}          # prefix node key -> (node, [slots])
        while self.queue and self._free:
            req = self.queue.popleft()
            reason = self._abort_reason(req)
            if reason:
                # deadline enforcement point 1 of 3: at admit — a request
                # that expired queued never takes a lane from a live one
                if reason == "deadline":
                    self.deadline_count += 1
                self._record_abort(req, reason=reason)
                continue
            slot = self._free.popleft()
            plen = len(req.prompt)
            timer = self._timers.get(req.rid)
            if timer is not None:
                timer.to("prefill")
            if self.chunked:
                skip, node = 0, None
                if self.prefix_store is not None \
                        and not self.engine.prefix_len(req):
                    try:
                        skip, node = self.prefix_store.match(req.prompt)
                    except PrefixLookupError:
                        # store outage: degrade to a cold prefill — the
                        # request costs more, it does not fail
                        self.prefix_lookup_failures += 1
                        skip, node = 0, None
                chunks, starts, seq_ends = self._plan_chunks(req, skip=skip)
                self._prefilling.append(_Prefill(
                    slot=slot, req=req, chunks=chunks, starts=starts,
                    seq_ends=seq_ends, t_admit=self.clock(),
                    cached_len=skip))
                if node is not None:
                    clones.setdefault(node.key, (node, []))[1].append(slot)
                    self.prefix_hits += 1
                    self.prefix_hit_tokens += skip
                self.prefill_tokens_computed += plen - skip
                self.prefill_tokens_served += plen
                self._m.prefill_tokens.labels(source="computed") \
                    .inc(plen - skip)
                self._m.prefill_tokens.labels(source="cached").inc(skip)
                n += 1
                continue
            if self.n_active:
                self.full_prefill_stalls += 1
            bucket = max(self._bucket(plen), plen)
            t_admit = self.clock()
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :plen] = req.prompt
            kw = {}
            true_len = plen + self.engine.prefix_len(req)
            if req.frames is not None:
                kw["frames"] = jnp.asarray(req.frames)[None]
            if self.engine.prefix_len(req):
                kw["patches"] = jnp.asarray(req.patches)[None]
            self.prefill_tokens_computed += plen
            self.prefill_tokens_served += plen
            self._m.prefill_tokens.labels(source="computed").inc(plen)
            logits, req_cache = self.engine.prefill(padded, true_len, kw)
            self.pool = self.engine.insert(self.pool, slot, req_cache)
            self._start_lane(slot, req, logits, t_admit)
            n += 1
        for node, slots in clones.values():
            prefix = ExistingPrefix(cache=self.prefix_store.assemble(node),
                                    common_len=node.n_tokens)
            self.pool = self.engine.bulk_insert(
                self.pool, np.asarray(slots, np.int32), prefix)
        self._m.queue_depth.set(len(self.queue))
        return n

    def _start_lane(self, slot: int, req: GenerateRequest, logits,
                    t_admit: float, done: list | None = None,
                    cached_len: int = 0) -> None:
        """Prefill finished: seed the lane with the prompt's sampled first
        token (index 0 of the request's key schedule) and write the lane's
        PRNG state (seed, next step index) into the pool."""
        with span("repro.first_token", rid=req.rid):
            sp = req.sampling or GREEDY
            first = self.engine.sample_first(logits, sp)
            self.pool = self.engine.set_sampling_state(self.pool, slot,
                                                       sp.seed, 1)
            now = self.clock()
            timer = self._timers.get(req.rid)
            if timer is not None:
                timer.to("decode")
            if req.arrival is not None:
                self._m.ttft.observe(now - req.arrival)
            lane = _Lane(req=req, tokens=[first],
                         remaining=req.max_new_tokens - 1,
                         t_admit=t_admit, t_first=now, token_times=[now],
                         cached_len=cached_len)
            self.lanes[slot] = lane
            self._m.active_lanes.set(self.n_active)
            self._next_tok[slot, 0] = first
            reason = self._token_reason(lane, first)   # evaluated exactly once
            self._emit(lane, first, 0, reason)
            if reason is not None:
                result = self._finish(slot, reason)
                if done is not None:
                    done.append(result)

    def _step_prefill(self, done: list) -> bool:
        """Advance ONE chunk of the oldest mid-prefill lane."""
        if not self._prefilling:
            return False
        pf = self._prefilling[0]
        k = pf.next_chunk
        final = k == len(pf.chunks) - 1
        kw = {}
        if k == 0 and self.engine.prefix_len(pf.req):
            kw["patches"] = jnp.asarray(pf.req.patches)[None]
        with span("repro.prefill_chunk", rid=pf.req.rid, chunk=k,
                  tokens=pf.seq_ends[k] - pf.starts[k]):
            logits, self.pool = self.engine.prefill_chunk(
                self.pool, pf.slot, pf.chunks[k], pf.starts[k],
                pf.seq_ends[k], final, kw)
        pf.next_chunk += 1
        if final:
            self._prefilling.popleft()
            self._intern_prefix(pf)
            self._start_lane(pf.slot, pf.req, logits, pf.t_admit, done,
                             cached_len=pf.cached_len)
        return True

    def _intern_prefix(self, pf: _Prefill) -> None:
        """Intern a finished prompt's block-aligned pages into the store.

        Runs at activation, when the lane holds the whole prompt's K/V +
        LOP features. Chunk boundaries are bitwise-reproducible (the
        ``(start, kv_len)`` carry contract), so pages recomputed by a
        later miss are identical to the ones interned here — reuse is
        token-exact by construction. The ``missing`` pre-check keeps the
        common already-interned case free of a pool extract.
        """
        store = self.prefix_store
        if store is None or self.engine.prefix_len(pf.req):
            return
        n = (len(pf.req.prompt) // store.block) * store.block
        if not n:
            return
        with span("repro.intern_prefix", rid=pf.req.rid):
            if store.missing(pf.req.prompt[:n]):
                lane = self.engine.extract(self.pool, pf.slot)
                store.insert(pf.req.prompt[:n], lane)

    # ---------------- decode / evict ----------------

    def _token_reason(self, lane: _Lane, tok: int) -> str | None:
        """Finish reason after appending ``tok``, or None to continue.
        Precedence: eos > stop sequence > token budget."""
        req = lane.req
        if req.eos_id is not None and tok == req.eos_id:
            return "eos"
        for seq in req.stop:
            if len(seq) <= len(lane.tokens) \
                    and tuple(lane.tokens[-len(seq):]) == seq:
                return "stop"
        if lane.remaining <= 0:
            return "length"
        return None

    def _emit(self, lane: _Lane, tok: int, index: int,
              reason: str | None) -> None:
        """Stream one token to the request's ``on_token`` callback."""
        cb = lane.req.on_token
        if cb is not None:
            cb(StepResult(rid=lane.req.rid, token=tok, index=index,
                          finished=reason is not None,
                          finish_reason=reason or ""))

    def _expired(self, req: GenerateRequest) -> bool:
        """Whether ``req``'s latency budget (``deadline_ms``, measured
        from arrival) has run out."""
        if req.deadline_ms is None or req.arrival is None:
            return False
        return (self.clock() - req.arrival) * 1e3 > req.deadline_ms

    def _abort_reason(self, req: GenerateRequest) -> str | None:
        """Terminal reason forcing ``req`` out mid-flight, or None.
        Cancellation wins over deadline (the caller already gave up)."""
        if req.cancelled:
            return "cancelled"
        if self._expired(req):
            return "deadline"
        return None

    def _sweep_terminal(self, done: list) -> None:
        """Retire cancelled and deadline-expired requests wherever they
        are in the lifecycle: queued (never admitted), mid-prefill (lane
        released between chunks; its partial K/V goes stale like any
        evicted lane's), or decoding. Runs at the top of every serve
        cycle, which is what enforces deadlines between prefill chunks
        and per decode sweep."""
        if self.queue and any(self._abort_reason(r) for r in self.queue):
            kept: deque[GenerateRequest] = deque()
            for req in self.queue:
                reason = self._abort_reason(req)
                if reason:
                    if reason == "deadline":
                        self.deadline_count += 1
                    done.append(self._record_abort(req, reason=reason))
                else:
                    kept.append(req)
            self.queue = kept
            self._m.queue_depth.set(len(self.queue))
        if self._prefilling and any(self._abort_reason(p.req)
                                    for p in self._prefilling):
            kept_p: deque[_Prefill] = deque()
            for pf in self._prefilling:
                reason = self._abort_reason(pf.req)
                if reason:
                    if reason == "deadline":
                        self.deadline_count += 1
                    done.append(self._record_abort(pf.req,
                                                   t_admit=pf.t_admit,
                                                   reason=reason))
                    self._free.append(pf.slot)
                else:
                    kept_p.append(pf)
            self._prefilling = kept_p
        for slot, lane in enumerate(self.lanes):
            if lane is not None:
                reason = self._abort_reason(lane.req)
                if reason:
                    if reason == "deadline":
                        self.deadline_count += 1
                    done.append(self._finish(slot, reason))

    def _lane_kv_len(self, slot: int) -> int:
        """Committed cache length of lane ``slot``: positions [0, L) hold
        written K/V; the pending ``_next_tok`` will occupy position L."""
        lane = self.lanes[slot]
        return (self.engine.prefix_len(lane.req) + len(lane.req.prompt)
                + len(lane.tokens) - 1)

    def _spec_gamma(self) -> int:
        """This round's draft length: the min over active lanes of the
        per-request γ (``sampling.gamma``, 0 = scheduler default), the
        lane's remaining token budget, and its capacity headroom — the
        verify chunk writes γ+1 rows at [L, L+γ+1), so γ shrinks at the
        slot boundary (never past ``max_len``). Returns 0 when any lane
        can't speculate, falling the whole cycle back to plain decode."""
        g = None
        for slot, lane in enumerate(self.lanes):
            if lane is None:
                continue
            if lane.no_spec:
                # a faulted or watchdog-tripped lane never drafts again;
                # the whole cycle degrades to plain decode (the batched
                # draft/verify launches can't exclude one lane)
                return 0
            sp = lane.req.sampling or GREEDY
            lane_g = sp.gamma if sp.gamma > 0 else self.gamma
            lane_g = min(lane_g, lane.remaining)
            if self.capacity:
                lane_g = min(lane_g,
                             self.capacity - 1 - self._lane_kv_len(slot))
            g = lane_g if g is None else min(g, lane_g)
        return max(0, g or 0)

    def _spec_round(self, g: int, temps, tks, tps,
                    done: list) -> None:
        """One speculative cycle: γ batched draft steps propose tokens for
        every active lane, then ONE chunk-shaped verify launch per lane
        scores all γ+1 positions exactly; the agreeing prefix plus the
        verifier's bonus token are emitted and the rejected tail is
        rewound (DESIGN.md §Speculative-decoding).

        Greedy lanes emit exactly the plain-decode stream (verify logits
        are bitwise the decode logits through the chunk-carry contract);
        sampled lanes draw draft i and its verify row with the SAME
        lane-local key (emission-indexed PRNG schedule), so the emitted
        stream equals the non-speculative same-seed stream. Finish
        reasons (eos > stop > length) are evaluated per emitted token —
        a hit inside the accepted window evicts the lane mid-round and
        the tokens past it are dropped, exactly as plain decode would
        never have generated them.
        """
        self.spec_rounds += 1
        active = [s for s, l in enumerate(self.lanes) if l is not None]
        base_e = {s: len(self.lanes[s].tokens) for s in active}
        base_len = {s: self._lane_kv_len(s) for s in active}
        drafts: dict[int, list[int]] = {s: [] for s in active}
        cur = self._next_tok.copy()
        for _ in range(g):
            with span("repro.spec_draft"):
                toks, self.pool = self.engine.draft(self.pool, cur, temps,
                                                    tks, tps)
            self.draft_launches += 1
            for s in active:
                d = int(toks[s])
                drafts[s].append(d)
                cur[s, 0] = d
        self.spec_drafted += g * len(active)

        for slot in active:
            lane = self.lanes[slot]
            start = base_len[slot]
            # the γ-clamp's guarantee, restated where a violation would
            # corrupt the lane: the verify writes rows [start, start+g+1)
            assert start + g + 1 <= self.capacity, (
                f"speculative verify would write past capacity "
                f"({start}+{g}+1 > {self.capacity})")
            block = np.concatenate(
                [self._next_tok[slot], np.asarray(drafts[slot], np.int32)]
            )[None, :]
            with span("repro.spec_verify"):
                logits, self.pool = self.engine.verify_chunk(
                    self.pool, slot, block, start)
            self.spec_verify_launches += 1
            if not bool(np.isfinite(np.asarray(logits)).all()):
                # poisoned verify logits: rewind the whole round for this
                # lane (g drafts + 1 verify append) and retire it from
                # speculation — next cycle's plain decode recomputes the
                # token through the NaN-guard/retry path
                self.pool = self.engine.rollback(self.pool, slot, g + 1)
                lane.no_spec = True
                self.fault_events += 1
                self._m.fault_events.inc()
                self.fault_rids.add(lane.req.rid)
                continue
            sp = lane.req.sampling or GREEDY
            targets = self.engine.sample_block(logits, sp, base_e[slot])
            j = 0
            while j < g and drafts[slot][j] == int(targets[j]):
                j += 1
            self.spec_accepted += j
            if j == 0:
                # drafting watchdog: a lane whose drafts are never
                # accepted is burning draft launches for nothing —
                # after ``spec_watchdog`` consecutive zero-accept rounds
                # it falls back to plain decode for good
                lane.zero_accept_rounds += 1
                if lane.zero_accept_rounds >= self.spec_watchdog:
                    lane.no_spec = True
                    self.spec_watchdog_trips += 1
            else:
                lane.zero_accept_rounds = 0
            finished = False
            for tok in (int(t) for t in targets[:j + 1]):
                abort = self._abort_reason(lane.req)
                if abort is not None:
                    # cancel/deadline fired mid-round: keep the tokens
                    # already committed this round, rewind the rest of
                    # the verify window, and retire the lane now
                    emitted = len(lane.tokens) - base_e[slot]
                    self.pool = self.engine.rollback(
                        self.pool, slot, g + 1 - emitted)
                    if abort == "deadline":
                        self.deadline_count += 1
                    done.append(self._finish(slot, abort))
                    finished = True
                    break
                idx = len(lane.tokens)
                lane.tokens.append(tok)
                lane.token_times.append(self.clock())
                lane.remaining -= 1
                self._next_tok[slot, 0] = tok
                self.spec_emitted += 1
                reason = self._token_reason(lane, tok)
                self._emit(lane, tok, idx, reason)
                if reason is not None:
                    done.append(self._finish(slot, reason))
                    finished = True
                    break
            if not finished and j < g:
                # rewind the rejected tail: lengths start+g+1 → start+j+1
                # (a finished lane was evicted — nothing to rewind)
                self.pool = self.engine.rollback(self.pool, slot, g - j)

    def _append_token(self, slot: int, tok: int, done: list) -> None:
        """Commit one emitted token to lane ``slot``: record it, stream
        it, and retire the lane if it hit a finish reason."""
        lane = self.lanes[slot]
        idx = len(lane.tokens)
        lane.tokens.append(tok)
        lane.token_times.append(self.clock())
        lane.remaining -= 1
        self._next_tok[slot, 0] = tok
        reason = self._token_reason(lane, tok)
        self._emit(lane, tok, idx, reason)
        if reason is not None:
            done.append(self._finish(slot, reason))

    def _recover_lane(self, slot: int, temps, tks, tps,
                      done: list) -> None:
        """Non-finite logits on lane ``slot`` this decode step: the
        recovery contract (DESIGN.md §Fault-tolerance). The poisoned
        append is rewound bitwise (``engine.rollback`` — K/V, scales,
        LOP features, PRNG step), drafting is permanently disabled for
        the lane, and the token is recomputed once through the engine's
        single-lane no-LOP retry. Only if the retry's logits are ALSO
        non-finite does the lane give up with reason ``"fault"`` (its
        tokens so far are delivered)."""
        lane = self.lanes[slot]
        self.fault_events += 1
        self._m.fault_events.inc()
        self.fault_rids.add(lane.req.rid)
        lane.no_spec = True
        self.pool = self.engine.rollback(self.pool, slot, 1)
        toks, ok, self.pool = self.engine.retry_step(
            self.pool, slot, self._next_tok, temps, tks, tps)
        if not bool(ok[slot]):
            self.pool = self.engine.rollback(self.pool, slot, 1)
            self.fault_finishes += 1
            self._m.fault_finishes.inc()
            done.append(self._finish(slot, "fault"))
            return
        self.fault_recoveries += 1
        self._m.fault_recoveries.inc()
        self._append_token(slot, int(toks[slot]), done)

    def step(self) -> list[FinishedRequest]:
        """One serve cycle: terminal sweep (cancellations + deadlines) +
        ≤1 prefill chunk + one sampled decode step over every active lane
        (or, in speculative mode, one draft-γ/verify round); returns
        completions. Under ``REPRO_PARANOID=1`` the invariant checker
        runs after every cycle."""
        done = self._step_inner()
        if self.paranoid:
            self.check_invariants()
        return done

    def _step_inner(self) -> list[FinishedRequest]:
        done: list[FinishedRequest] = []
        self._sweep_terminal(done)
        prefilling = self._step_prefill(done)
        if self.n_active == 0:
            return done
        if prefilling or self._prefilling:
            self.interleaved_decode_steps += 1
        temps = np.zeros(self.n_slots, np.float32)
        tks = np.zeros(self.n_slots, np.int32)
        tps = np.ones(self.n_slots, np.float32)
        for slot, lane in enumerate(self.lanes):
            if lane is None:
                continue
            sp = lane.req.sampling or GREEDY
            temps[slot] = sp.temperature
            tks[slot] = sp.top_k
            tps[slot] = sp.top_p
        if self.spec:
            g = self._spec_gamma()
            if g >= 1:
                self._spec_round(g, temps, tks, tps, done)
                return done
        toks, self.pool = self.engine.decode_step(
            self.pool, self._next_tok, temps, tks, tps)
        self.decode_launches += 1
        # per-lane logit-finiteness guard published by the engine (None:
        # an engine without the guard — every lane treated healthy)
        ok = getattr(self.engine, "last_ok", None)
        with span("repro.emit", tokens=self.n_active):
            for slot, lane in enumerate(self.lanes):
                if lane is None:
                    continue
                if ok is not None and not bool(ok[slot]):
                    self._recover_lane(slot, temps, tks, tps, done)
                    continue
                self._append_token(slot, int(toks[slot]), done)
        return done

    def _finish(self, slot: int, reason: str) -> FinishedRequest:
        lane = self.lanes[slot]
        with span("repro.finish", rid=lane.req.rid):
            res = FinishedRequest(
                rid=lane.req.rid, prompt_len=len(lane.req.prompt),
                tokens=lane.tokens, finish_reason=reason,
                t_arrival=lane.req.arrival, t_admit=lane.t_admit,
                t_first=lane.t_first, t_done=self.clock(),
                token_times=lane.token_times, cached_len=lane.cached_len)
            self.pool = self.engine.evict(self.pool, slot)
            self.lanes[slot] = None
            self._free.append(slot)
            self._next_tok[slot, 0] = 0
            self.results.append(res)
            self._m.active_lanes.set(self.n_active)
            self._publish_finish(res, reason)
        return res

    def _record_abort(self, req: GenerateRequest, t_admit: float = 0.0,
                      reason: str = "cancelled") -> FinishedRequest:
        """A request retired before emitting any token (cancelled,
        deadline-expired, or load-shed)."""
        now = self.clock()
        res = FinishedRequest(
            rid=req.rid, prompt_len=len(req.prompt), tokens=[],
            finish_reason=reason,
            t_arrival=req.arrival if req.arrival is not None else now,
            t_admit=t_admit or now, t_first=now, t_done=now,
            token_times=[])
        self.results.append(res)
        self._publish_finish(res, reason)
        return res

    def _publish_finish(self, res: FinishedRequest, reason: str) -> None:
        """Registry side of retirement: finish-reason counters, the
        request's stage spans, and its latency observations — the same
        numbers the int attributes / FinishedRequest fields carry, as
        exported series (DESIGN.md §Serving-frontend)."""
        self._m.requests.labels(outcome=reason).inc()
        if reason == "deadline":
            self._m.deadline.inc()
        self._m.tokens.inc(len(res.tokens))
        self._m.e2e.observe(res.latency)
        for gap in res.itl:
            self._m.itl.observe(gap)
        timer = self._timers.pop(res.rid, None)
        if timer is not None:
            for stage, span in timer.finish().items():
                self._m.stage_seconds.labels(stage=stage).observe(span)

    # ---------------- invariants (REPRO_PARANOID=1) ----------------

    def check_invariants(self) -> None:
        """Cross-check host bookkeeping against device state — the
        contracts every fault-recovery path must preserve (DESIGN.md
        §Fault-tolerance). Runs after every ``step()`` under
        ``REPRO_PARANOID=1``; cheap enough for CI chaos runs (a few
        scalar pulls per cycle, no page reads).

        - slot partition: every slot is exactly one of occupied (a live
          lane), reserved (mid-chunked-prefill) or free
        - the pool's ``active`` mask equals the occupied set (reserved
          lanes stay inactive until their final chunk)
        - per-lane ``lengths`` stay within pool capacity, and an occupied
          lane's device length equals its host-side committed length
          (prefix + prompt + emissions − 1 pending)
        - PRNG-step monotonicity: a sampled lane's ``sample_step`` is
          non-negative and equals its emission count, so rollback/retry
          cycles net to exactly the tokens delivered (greedy lanes never
          read their counter and are exempt)
        - the prefix store's own structural invariants hold
        """
        occupied = {s for s, l in enumerate(self.lanes) if l is not None}
        reserved = {pf.slot for pf in self._prefilling}
        free = set(self._free)
        assert occupied.isdisjoint(reserved) and occupied.isdisjoint(free) \
            and reserved.isdisjoint(free), (
            f"slot sets overlap: occupied={occupied} reserved={reserved} "
            f"free={free}")
        assert len(free) == len(self._free), "duplicate slots in free list"
        assert occupied | reserved | free == set(range(self.n_slots)), (
            f"slot partition incomplete: occupied={occupied} "
            f"reserved={reserved} free={free} n_slots={self.n_slots}")
        if "active" in self.pool:
            dev_active = {int(s) for s in
                          np.flatnonzero(np.asarray(self.pool["active"]))}
            assert dev_active == occupied, (
                f"pool active mask {dev_active} != occupied lanes "
                f"{occupied}")
        lengths = np.asarray(self.pool["lengths"])
        if self.capacity:
            assert int(lengths.max(initial=0)) <= self.capacity, (
                f"lane length {int(lengths.max())} exceeds pool capacity "
                f"{self.capacity}")
            for slot in occupied:
                want = self._lane_kv_len(slot)
                assert int(lengths[slot]) == want, (
                    f"slot {slot}: device length {int(lengths[slot])} != "
                    f"host committed length {want}")
        if "sample_step" in self.pool:
            steps = np.asarray(self.pool["sample_step"])
            assert int(steps.min(initial=0)) >= 0, (
                f"negative sample_step: {steps}")
            for slot in occupied:
                lane = self.lanes[slot]
                sp = lane.req.sampling
                if sp is None or sp.temperature <= 0.0:
                    continue        # greedy lanes never read the counter
                assert int(steps[slot]) == len(lane.tokens), (
                    f"slot {slot}: sample_step {int(steps[slot])} != "
                    f"emissions {len(lane.tokens)} — a rollback/retry "
                    f"desynced the PRNG schedule")
        if self.prefix_store is not None:
            self.prefix_store.check_invariants()

    def run_to_completion(self) -> list[FinishedRequest]:
        """Drain queue + lanes (all requests already submitted)."""
        while self.has_work():
            with span("repro.cycle"):
                self.admit()
                self.step()
        return self.results


# ---------------------------------------------------------------------------
# Lockstep reference path — the batch-1 implementation of the same protocol
# ---------------------------------------------------------------------------

# engines cached per (cfg, use_lop, max_len) so the N-request verify replay
# compiles each shape once, not once per request; the jitted closures take
# qp as an argument, so the cached engine is re-pointed per call
_REF_ENGINES: dict = {}


def _ref_engine(cfg, qp, use_lop: bool, max_len: int) -> PooledEngine:
    key = (cfg, use_lop, max_len)
    eng = _REF_ENGINES.get(key)
    if eng is None:
        eng = PooledEngine(cfg, qp, max_len=max_len, use_lop=use_lop)
        _REF_ENGINES[key] = eng
    eng.qp = qp
    return eng


def lockstep_generate(cfg, qp, prompt, max_new_tokens: int, *,
                      max_len: int, use_lop: bool = True,
                      eos_id: int | None = None, frames=None,
                      patches=None, sampling: SamplingParams | None = None,
                      stop=(), on_token=None, cancel=None,
                      engine=None) -> list:
    """Single-request reference path: whole-prompt prefill + decode,
    driven through the SAME :class:`InferenceEngine` protocol and the
    same sampler as the pooled scheduler — per
    :class:`SamplingParams`, greedy requests reproduce the pool
    bitwise and seeded requests draw from identical lane-local keys.

    ``max_len`` must match the pool's (same cache capacity → same LOP
    block top-K budget AND the same prefill-attention operand shapes the
    chunked path sees) for token-exact agreement with the scheduler.
    """
    eng = engine if engine is not None else _ref_engine(cfg, qp, use_lop,
                                                        max_len)
    sp = sampling or GREEDY
    req = GenerateRequest(rid=-1, prompt=np.asarray(prompt),
                          max_new_tokens=max_new_tokens, eos_id=eos_id,
                          sampling=sp, stop=stop, on_token=on_token,
                          cancel=cancel, frames=frames, patches=patches)
    kw = {}
    true_len = len(req.prompt) + eng.prefix_len(req)
    if frames is not None:
        kw["frames"] = jnp.asarray(frames)[None]
    if eng.prefix_len(req):
        kw["patches"] = jnp.asarray(patches)[None]
    logits, cache = eng.prefill(np.asarray(prompt)[None], true_len, kw)
    # the batch-1 cache carries the same PRNG leaves the pool does: seed +
    # next step index (1 — index 0 is the prefill's sample_first draw)
    cache = dict(cache)
    cache["seed"] = jnp.full((1,), sp.seed, jnp.int32)
    cache["sample_step"] = jnp.ones((1,), jnp.int32)
    toks: list = []

    def append(tok: int) -> str | None:
        toks.append(tok)
        if req.eos_id is not None and tok == req.eos_id:
            reason = "eos"
        elif any(len(s) <= len(toks) and tuple(toks[-len(s):]) == s
                 for s in req.stop):
            reason = "stop"
        elif len(toks) >= max_new_tokens:
            reason = "length"
        else:
            reason = None
        if on_token is not None:
            on_token(StepResult(rid=req.rid, token=tok, index=len(toks) - 1,
                                finished=reason is not None,
                                finish_reason=reason or ""))
        return reason

    reason = append(eng.sample_first(logits, sp))
    sp_arrs = (np.asarray([sp.temperature], np.float32),
               np.asarray([sp.top_k], np.int32),
               np.asarray([sp.top_p], np.float32))
    while reason is None and not req.cancelled:
        temps, tks, tps = sp_arrs
        nxt, cache = eng.decode_step(
            cache, np.asarray([[toks[-1]]], np.int32), temps, tks, tps)
        reason = append(int(nxt[0]))
    return toks
