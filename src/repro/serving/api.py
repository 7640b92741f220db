"""Typed serving API: sampling params, requests, results, engine protocol.

The serving stack's cross-layer contract (DESIGN.md §Serving-API), in the
spirit of the paper's absmax barrier: one standardized interface so the
control plane (:mod:`repro.serving.scheduler`), the compute engine
(:mod:`repro.serving.engine` wrapped by :class:`PooledEngine`) and the
drivers (``launch/serve.py``, examples, benchmarks) compose without
bespoke glue or per-model-family branches. The shape follows JetStream's
``engine_api`` (prefill / insert / generate + declared capabilities):

  * :class:`SamplingParams` — frozen per-request decode policy
    (greedy / temperature / top-k / top-p + PRNG seed). The sampling
    contract lives in :mod:`repro.serving.sampling`: greedy is bitwise
    argmax, and a seeded request decodes the same tokens pooled or
    alone.
  * :class:`GenerateRequest` — frozen request envelope: prompt, budget,
    eos, stop token sequences, an optional streaming ``on_token``
    callback and a mutable :class:`CancelToken` handle.
  * :class:`StepResult` — one streamed token (what ``on_token``
    receives, in emission order, ``finished`` on the last).
  * :class:`FinishedRequest` — the completed request: tokens, finish
    reason, and the full latency breakdown including per-token
    timestamps (inter-token-latency telemetry).
  * :class:`ExistingPrefix` — a computed, interned prefill prefix
    (block-aligned cache pages + the token count they cover) that
    ``bulk_insert`` clones into many lanes at once; chunked prefill then
    resumes from the cached block boundary (JetStream's
    ``ExistingPrefix`` / ``bulk_insert`` shape — DESIGN.md
    §Prefix-caching).
  * :class:`InferenceEngine` — the protocol the scheduler speaks:
    ``prefill`` / ``prefill_chunk`` / ``insert`` / ``bulk_insert`` /
    ``decode_step`` / ``evict`` plus *declared capabilities*
    (``supports_chunked``, ``exact_length_prefill``, ``state_kind``,
    ``has_image_prefix``, ``prefix_block``).
    Model-family names appear ONLY in capability declarations —
    :class:`PooledEngine` is the one place that maps family → behaviour;
    the scheduler dispatches on capabilities alone.

Sampling state lives IN the pool (``seed`` / ``sample_step`` cache
leaves): ``decode_step`` reads each lane's PRNG schedule in-graph and
advances it with the lane, so cloned or migrated lanes keep same-seed
bitwise reproducibility with no host round-trip
(``set_sampling_state`` seeds a lane once, at activation).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Protocol, runtime_checkable

import jax
import numpy as np

from repro.serving import cache as _cache
from repro.serving import faults as _faults
from repro.serving.engine import (draft_step, guard_logits, prefill,
                                  prefill_chunk as chunk_forward, serve_step)
from repro.serving.metrics import span
from repro.serving.sampling import sample_with_seed

# ---------------------------------------------------------------------------
# Request-side dataclasses
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class SamplingParams:
    """Per-request decode policy. ``temperature <= 0`` is the greedy fast
    path (bitwise argmax — reproduces the pre-API scheduler tokens);
    ``top_k <= 0`` and ``top_p >= 1`` disable their filters. ``seed``
    drives the lane-local key schedule
    (:func:`repro.serving.sampling.lane_keys`), so two runs of the same
    request with the same seed draw identical tokens regardless of what
    else shares the pool. ``gamma`` is the request's speculative draft
    length — tokens proposed per verify launch when the scheduler runs
    in speculative mode (``0`` defers to the scheduler's default γ;
    ignored outside speculative mode)."""
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    gamma: int = 0

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


GREEDY = SamplingParams()


class CancelToken:
    """Mutable cancellation handle carried by a frozen request.

    The submitter keeps a reference and calls :meth:`cancel`; the
    scheduler observes it at the next serve cycle and retires the
    request mid-flight (queued, mid-prefill, or mid-decode) with
    ``finish_reason="cancelled"``.
    """

    __slots__ = ("_cancelled",)

    def __init__(self) -> None:
        self._cancelled = False

    def cancel(self) -> None:
        self._cancelled = True

    @property
    def cancelled(self) -> bool:
        return self._cancelled

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        return f"CancelToken(cancelled={self._cancelled})"


@dataclass(frozen=True)
class StepResult:
    """One streamed token, delivered to ``on_token`` as it decodes.

    ``index`` is the 0-based position in the generated stream (the
    prefill-seeded first token is index 0). ``finished`` marks the
    request's final token, with ``finish_reason`` set to
    ``"eos" | "stop" | "length"`` (a cancellation emits no token, so a
    cancelled request's last delivered StepResult has
    ``finished=False``)."""
    rid: int
    token: int
    index: int
    finished: bool
    finish_reason: str = ""


@dataclass(frozen=True, eq=False)
class GenerateRequest:
    """One generation request entering the queue (frozen envelope).

    ``stop`` holds token *sequences*: decoding finishes with reason
    ``"stop"`` as soon as the generated stream ends with any of them
    (the matched suffix stays in ``tokens`` — callers trim if they want
    it hidden). ``on_token`` streams every emitted token in order;
    ``cancel`` is the mid-flight abort handle. ``arrival`` is stamped at
    submit when left None (the scheduler re-creates the frozen record
    via ``dataclasses.replace``). ``deadline_ms`` is the request's
    latency budget measured from ``arrival``: the scheduler enforces it
    at admit, between prefill chunks, and per decode sweep, retiring the
    request with reason ``"deadline"`` — an expired request never holds
    a lane another request could use (DESIGN.md §Fault-tolerance)."""
    rid: int
    prompt: np.ndarray                 # int32 [prompt_len]
    max_new_tokens: int
    eos_id: int | None = None
    sampling: SamplingParams = GREEDY
    stop: tuple = ()                   # tuple[tuple[int, ...], ...]
    on_token: Callable[[StepResult], None] | None = None
    cancel: CancelToken | None = None
    arrival: float | None = None       # driver-set; submit() stamps None
    deadline_ms: float | None = None   # latency budget from arrival
    frames: np.ndarray | None = None   # encdec audio frames [S_enc, D]
    patches: np.ndarray | None = None  # vlm patch embeds [n_img, D]

    def __post_init__(self):
        # canonicalize stop sequences to hashable int tuples (accepts any
        # iterable-of-iterables; drops empty sequences)
        stop = tuple(tuple(int(t) for t in seq) for seq in self.stop)
        object.__setattr__(self, "stop", tuple(s for s in stop if s))

    @property
    def cancelled(self) -> bool:
        return self.cancel is not None and self.cancel.cancelled


@dataclass(frozen=True, eq=False)
class FinishedRequest:
    """Completed request: emitted tokens + full latency breakdown.

    ``token_times`` stamps each token's host-visible emission (index 0
    == ``t_first``), the raw series behind inter-token-latency
    percentiles. A request cancelled before its first token finishes
    with empty ``tokens`` and ``t_first == t_done``."""
    rid: int
    prompt_len: int
    tokens: list                       # list[int], emission order
    finish_reason: str                 # "eos" | "stop" | "length" |
    #                                    "cancelled" | "deadline" |
    #                                    "shed" | "fault"
    t_arrival: float = 0.0
    t_admit: float = 0.0               # prefill started (lane granted)
    t_first: float = 0.0               # first token emitted (TTFT end)
    t_done: float = 0.0
    token_times: list = field(default_factory=list)
    cached_len: int = 0                # prompt tokens served from the
    #                                    prefix cache (0 = cold prefill)

    @property
    def ttft(self) -> float:
        return self.t_first - self.t_arrival

    @property
    def latency(self) -> float:
        return self.t_done - self.t_arrival

    @property
    def itl(self) -> list:
        """Inter-token latencies (seconds), one per token after the first."""
        return [b - a for a, b in zip(self.token_times, self.token_times[1:])]


@dataclass(frozen=True)
class ExistingPrefix:
    """A computed prefill prefix to resume from (JetStream-style).

    ``cache`` is a batch-1 pytree of block-aligned prefix pages — the
    K/V, scales AND packed LOP feature rows for the first ``common_len``
    stream positions, plus ``lengths == [common_len]`` — normally
    assembled by :meth:`repro.serving.cache.PrefixStore.assemble`.
    ``engine.bulk_insert`` clones it into many lanes at once; each lane
    then resumes chunked prefill at ``start = common_len`` through the
    bitwise ``(start, kv_len)`` chunk-carry contract, so a prefix-hit
    request decodes token-identically to a cold one."""
    cache: dict
    common_len: int


# ---------------------------------------------------------------------------
# Engine protocol
# ---------------------------------------------------------------------------


@runtime_checkable
class InferenceEngine(Protocol):
    """What the scheduler requires of a compute engine.

    Capabilities (attributes) replace the scheduler's old model-family
    name checks — an engine *declares* how it must be driven:

      ``supports_chunked``     prompts may split into fixed-size chunks
                               interleaved with decode (causal attention
                               with split-invariant per-token compute).
      ``exact_length_prefill`` prompts must prefill at their exact
                               length — no pow2 pad buckets (recurrent
                               state integrates every position, encdec
                               compiles against its encoder frames).
      ``has_image_prefix``     requests may carry ``patches`` that
                               occupy cache positions before the text.
      ``state_kind``           what a lane holds: ``"paged-kv"``,
                               ``"recurrent"``, ``"hybrid"`` or
                               ``"paged-kv+cross"`` (informational).
      ``chunk_tokens``         the fixed chunk width of the chunked
                               regime.
      ``prefix_block``         token-block granularity of prefix-cache
                               pages (0 = engine cannot resume from a
                               cached prefix — recurrent state is not
                               positional).
      ``supports_speculative`` the engine can draft cheap tokens
                               (``draft``), score them exactly in one
                               chunk-shaped launch (``verify_chunk``) and
                               rewind rejected cache writes
                               (``rollback``) — requires rewindable
                               positional state, so it holds exactly
                               where chunked prefill does (DESIGN.md
                               §Speculative-decoding).

    Methods mirror the lifecycle: ``prefill`` (whole prompt → batch-1
    cache), ``prefill_chunk`` (one chunk against a reserved pool lane),
    ``insert`` (batch-1 cache → lane), ``bulk_insert`` (one
    :class:`ExistingPrefix` → many lanes), ``extract`` (lane → batch-1
    cache, for interning), ``decode_step`` (advance every lane one token
    AND sample, in one dispatch), ``evict`` (retire a lane).
    ``sample_first`` seeds a lane from prefill logits through the same
    sampler the decode step uses; ``set_sampling_state`` writes the
    lane's in-pool PRNG schedule at activation.

    Fault contract (DESIGN.md §Fault-tolerance): after ``decode_step``
    the engine publishes ``last_ok`` (np bool [B]) — each lane's logit
    finiteness for THAT step, computed in-graph; engines without the
    guard simply never set it and the scheduler treats every lane as
    healthy. ``retry_step`` recomputes ONE quarantined lane's token with
    degraded features (LOP disabled) against a pool already rewound by
    ``rollback``, leaving every other lane's state untouched.
    """

    supports_chunked: bool
    exact_length_prefill: bool
    has_image_prefix: bool
    state_kind: str
    chunk_tokens: int
    prefix_block: int
    supports_speculative: bool

    def init_pool(self, n_slots: int): ...

    def prefix_len(self, req: GenerateRequest) -> int: ...

    def prefill(self, tokens, true_len, kw): ...

    def prefill_chunk(self, pool, slot, tokens, start, seq_end, activate,
                      kw): ...

    def insert(self, pool, slot, req_cache): ...

    def bulk_insert(self, pool, slots, prefix: ExistingPrefix,
                    active: bool = False): ...

    def extract(self, pool, slot): ...

    def decode_step(self, pool, tokens, temperature, top_k, top_p): ...

    def retry_step(self, pool, slot, tokens, temperature, top_k,
                   top_p): ...

    def draft(self, pool, tokens, temperature, top_k, top_p): ...

    def verify_chunk(self, pool, slot, tokens, start): ...

    def rollback(self, pool, slot, n: int): ...

    def sample_block(self, logits, sampling: "SamplingParams",
                     first_step: int): ...

    def evict(self, pool, slot): ...

    def sample_first(self, logits, sampling: SamplingParams,
                     seed_step: int = 0) -> int: ...

    def set_sampling_state(self, pool, slot, seed: int, step: int): ...


_STATE_KINDS = {"dense": "paged-kv", "moe": "paged-kv", "vlm": "paged-kv",
                "hybrid": "hybrid", "ssm": "recurrent",
                "encdec": "paged-kv+cross"}


class PooledEngine:
    """:class:`InferenceEngine` over the slot-paged serving stack.

    Owns the quantized params, the jit caches (one prefill compile per
    shape bucket, one chunk compile per chunk shape, one fused
    decode+sample step) and the capability declarations for ``cfg``'s
    family — the ONLY place in the serving control plane where family
    names appear. The decode step fuses
    :func:`repro.serving.engine.serve_step` with the batched sampler
    (:mod:`repro.serving.sampling`) so sampling adds no extra dispatch.
    """

    def __init__(self, cfg, qp, *, max_len: int, use_lop: bool = True,
                 chunk_tokens: int | None = None,
                 draft_layers: int | None = None,
                 draft_k: int | None = None,
                 shape_log: str | None = None):
        import jax.numpy as jnp  # local alias for the jitted closures

        self.cfg = cfg
        self.qp = qp
        self.max_len = max_len
        self.use_lop = use_lop
        if shape_log is not None:
            # log-and-sweep sidecar (DESIGN.md §Autotuning): every
            # distinct kernel dispatch shape this engine traces is
            # recorded so `python -m repro.kernels.autotune --from-log`
            # can sweep the shapes production traffic actually serves
            from repro.kernels import autotune as _tune
            _tune.start_shape_log(shape_log)
        self.chunk_tokens = chunk_tokens or cfg.lop_block
        # speculative draft knobs: layer-stack prefix depth and degraded
        # LOP selection budget (None = config's serving budget)
        self.draft_layers = min(cfg.n_layers, max(1, (
            draft_layers if draft_layers is not None
            else cfg.n_layers // 2)))
        self.draft_k = 1 if draft_k is None else max(1, draft_k)
        # ---- capability declarations (family → behaviour, once) ----
        # moe chunks too: its served experts are dropless, so a token's
        # output never depends on the rows it shares a call with
        self.supports_chunked = cfg.family in ("dense", "moe", "vlm")
        self.exact_length_prefill = cfg.family in ("hybrid", "ssm",
                                                   "encdec")
        self.has_image_prefix = cfg.family == "vlm"
        self.state_kind = _STATE_KINDS[cfg.family]
        # prefix pages are lop_block-aligned (cache pages already are),
        # and resume rides the chunked (start, kv_len) carry — so prefix
        # caching exists exactly where chunked prefill does
        self.prefix_block = cfg.lop_block if self.supports_chunked else 0
        # speculation needs rewindable positional state, a chunk-shaped
        # verify launch AND a uniform layer stack to truncate for the
        # draft (engine.draft_step): dense and vlm
        self.supports_speculative = cfg.family in ("dense", "vlm")

        self.prefill_compiles = 0
        self._fns: dict = {}
        self._jnp = jnp

        def step_and_sample(qp_, pool, tokens, temp, tk, tp, fadd):
            # the PRNG schedule lives in the pool: seed is per-request,
            # sample_step counts the lane's emissions — advanced in-graph
            # for active lanes, so a cloned/migrated lane samples its
            # same-seed token stream with no host round-trip. ``fadd``
            # is the fault-injection offset (zeros in production) and
            # ``ok`` the per-lane logit-finiteness guard — both ride the
            # same compile, so fault tolerance costs one add + reduce
            logits, pool = serve_step(cfg, qp_, pool, tokens,
                                      use_lop=use_lop)
            logits, ok = guard_logits(logits, fadd)
            seeds, steps = pool["seed"], pool["sample_step"]
            toks = sample_with_seed(logits, seeds, steps, temp, tk, tp)
            pool = dict(pool)
            adv = (pool["active"].astype(jnp.int32) if "active" in pool
                   else jnp.int32(1))
            pool["sample_step"] = steps + adv
            return toks, ok, pool

        def step_greedy(qp_, pool, tokens, fadd):
            # all-greedy fast path: skip the sampler's sorts/softmax/
            # categorical entirely — bitwise the sampler's greedy branch
            # (both are argmax over the same logits); sample_step is not
            # advanced (greedy lanes never read it, and any lane that
            # later needs it is re-seeded at activation)
            logits, pool = serve_step(cfg, qp_, pool, tokens,
                                      use_lop=use_lop)
            logits, ok = guard_logits(logits, fadd)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), ok, pool

        def retry_one(qp_, pool, slot, tokens, temp, tk, tp, fadd,
                      sampled):
            # single-lane RECOVERY step: the faulted lane — already
            # rewound bitwise by rollback_slot — recomputes its token
            # with the LOP screen disabled (exact dense attention, the
            # bottom rung before giving the lane up) while every other
            # lane's state is frozen behind a masked active vector.
            # sample_step advances only on the sampled path, mirroring
            # the batched step's greedy/sampled asymmetry.
            act = pool["active"]
            only = act & (jnp.arange(act.shape[0]) == slot)
            pool = dict(pool)
            pool["active"] = only
            seeds, steps = pool["seed"], pool["sample_step"]
            logits, pool = serve_step(cfg, qp_, pool, tokens,
                                      use_lop=False)
            logits, ok = guard_logits(logits, fadd)
            if sampled:
                toks = sample_with_seed(logits, seeds, steps, temp, tk,
                                        tp)
            else:
                toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            pool = dict(pool)
            if sampled:
                pool["sample_step"] = steps + only.astype(jnp.int32)
            pool["active"] = act
            return toks, ok, pool

        def set_sampling(pool, slot, seed, step):
            pool = dict(pool)
            pool["seed"] = pool["seed"].at[slot].set(seed)
            pool["sample_step"] = pool["sample_step"].at[slot].set(step)
            return pool

        d_layers, d_k = self.draft_layers, self.draft_k

        def draft_and_sample(qp_, pool, tokens, temp, tk, tp):
            # speculative draft twin of step_and_sample: truncated layer
            # stack + degraded LOP budget, same in-pool PRNG schedule —
            # draft token i for a lane at emission count e samples at
            # step e+i-1, the SAME key verify re-samples that position
            # with, so a correct draft distribution maximizes agreement
            seeds, steps = pool["seed"], pool["sample_step"]
            logits, pool = draft_step(cfg, qp_, pool, tokens,
                                      draft_layers=d_layers, draft_k=d_k,
                                      use_lop=use_lop)
            toks = sample_with_seed(logits, seeds, steps, temp, tk, tp)
            pool = dict(pool)
            adv = (pool["active"].astype(jnp.int32) if "active" in pool
                   else jnp.int32(1))
            pool["sample_step"] = steps + adv
            return toks, pool

        def draft_greedy(qp_, pool, tokens):
            logits, pool = draft_step(cfg, qp_, pool, tokens,
                                      draft_layers=d_layers, draft_k=d_k,
                                      use_lop=use_lop)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), pool

        def retry_sampled(qp_, pool, slot, tokens, temp, tk, tp, fadd):
            return retry_one(qp_, pool, slot, tokens, temp, tk, tp, fadd,
                             True)

        def retry_greedy(qp_, pool, slot, tokens, fadd):
            return retry_one(qp_, pool, slot, tokens, None, None, None,
                             fadd, False)

        self._decode_fn = jax.jit(step_and_sample, donate_argnums=(1,))
        self._decode_greedy_fn = jax.jit(step_greedy, donate_argnums=(1,))
        # recovery retries compile lazily — a fault-free run never pays
        self._retry_fn = jax.jit(retry_sampled, donate_argnums=(1,))
        self._retry_greedy_fn = jax.jit(retry_greedy, donate_argnums=(1,))
        self._draft_fn = jax.jit(draft_and_sample, donate_argnums=(1,))
        self._draft_greedy_fn = jax.jit(draft_greedy, donate_argnums=(1,))
        self._rollback_fn = jax.jit(_cache.rollback_slot,
                                    donate_argnums=(0,))
        self._sample_fn = jax.jit(sample_with_seed)
        self._insert_fn = jax.jit(_cache.insert_slot, donate_argnums=(0,))
        self._bulk_insert_fn = jax.jit(
            lambda pool, slots, c, act: _cache.bulk_insert(pool, slots, c,
                                                           active=act),
            donate_argnums=(0,))
        self._extract_fn = jax.jit(_cache.extract_slot)
        self._evict_fn = jax.jit(_cache.evict_slot, donate_argnums=(0,))
        self._sampling_state_fn = jax.jit(set_sampling, donate_argnums=(0,))

    # ---------------- pool ----------------

    def init_pool(self, n_slots: int):
        return _cache.init_cache_pool(self.cfg, n_slots, self.max_len)

    def prefix_len(self, req: GenerateRequest) -> int:
        """Cache positions the request occupies before its text tokens."""
        if self.has_image_prefix and req.patches is not None:
            return len(req.patches)
        return 0

    # ---------------- prefill ----------------

    def _kw_key(self, kw) -> tuple:
        return tuple(sorted((k, v.shape) for k, v in kw.items()))

    def prefill(self, tokens, true_len, kw):
        """Whole-prompt prefill → (last logits [B, V], batch-1 cache).
        Compiles once per (padded length, extra-input shapes)."""
        key = ("prefill", tokens.shape[1]) + self._kw_key(kw)
        fn = self._fns.get(key)
        if fn is None:
            cfg, use_lop, max_len = self.cfg, self.use_lop, self.max_len
            fn = jax.jit(lambda qp, t, tl, kw_: prefill(
                cfg, qp, t, max_len=max_len, use_lop=use_lop, true_len=tl,
                **kw_))
            self._fns[key] = fn
            self.prefill_compiles += 1
        jnp = self._jnp
        return fn(self.qp, jnp.asarray(tokens), jnp.int32(true_len), kw)

    def prefill_chunk(self, pool, slot, tokens, start, seq_end, activate,
                      kw):
        """One fixed-shape chunk against the reserved lane ``slot``:
        extract → chunk forward → partial insert (``active`` flips live
        on the final chunk). Compiles once per (chunk width, extras)."""
        key = ("chunk", tokens.shape[1]) + self._kw_key(kw)
        fn = self._fns.get(key)
        if fn is None:
            cfg = self.cfg

            # the function's name names the compiled program
            # (``jit_prefill_chunk``) in a profiler trace
            def prefill_chunk(qp, pool_, slot_, toks, start_, seq_end_,
                              activate_, kw_):
                lane = _cache.extract_slot(pool_, slot_)
                logits, lane = chunk_forward(cfg, qp, toks, lane,
                                             start=start_, seq_end=seq_end_,
                                             **kw_)
                pool_ = _cache.insert_slot(pool_, slot_, lane,
                                           active=activate_)
                return logits, pool_

            fn = jax.jit(prefill_chunk, donate_argnums=(1,))
            self._fns[key] = fn
            self.prefill_compiles += 1
        jnp = self._jnp
        return fn(self.qp, pool, jnp.int32(slot), jnp.asarray(tokens),
                  jnp.int32(start), jnp.int32(seq_end),
                  jnp.asarray(activate), kw)

    def insert(self, pool, slot, req_cache):
        return self._insert_fn(pool, self._jnp.int32(slot), req_cache)

    def bulk_insert(self, pool, slots, prefix: ExistingPrefix,
                    active: bool = False):
        """Clone one :class:`ExistingPrefix` into lanes ``slots`` (int
        vector) — a single scatter per cache leaf, so N prefix hits cost
        one dispatch. Lanes land ``active=False`` by default: they are
        mid-prefill reservations that resume chunked prefill at
        ``prefix.common_len``. Compiles once per (lane count, prefix
        capacity) pair."""
        jnp = self._jnp
        return self._bulk_insert_fn(
            pool, jnp.asarray(np.asarray(slots, np.int32)), prefix.cache,
            jnp.asarray(bool(active)))

    def extract(self, pool, slot):
        """Batch-1 copy of lane ``slot`` (non-donating — the pool stays
        live); what the scheduler interns into the prefix store."""
        return self._extract_fn(pool, self._jnp.int32(slot))

    # ---------------- decode / evict ----------------

    def decode_step(self, pool, tokens, temperature, top_k, top_p):
        """Advance every active lane one token and sample it — ONE jitted
        dispatch (serve_step + batched sampler). → (tokens [B] i32, pool).
        Each lane's PRNG seed/step are read from the pool's sampling-state
        leaves and advanced in-graph. Inactive lanes' samples are garbage
        the scheduler never reads. When every lane is greedy (the default
        serving configuration) the sampler is skipped for a bare argmax
        step — bitwise the same tokens at the pre-API decode cost.

        Fault guard: the step computes each lane's logit-finiteness mask
        in-graph (``guard_logits``) and publishes it as ``self.last_ok``
        (np bool [B]); a lane marked False was poisoned THIS step — its
        sampled token is garbage and the scheduler must quarantine +
        recover it (``retry_step``) instead of emitting. An active
        :mod:`repro.serving.faults` plan injects NaN rows (and slow-step
        sleeps) here; with no plan the offset is zeros."""
        jnp = self._jnp
        n = np.asarray(tokens).shape[0]
        fadd = _faults.decode_fault_add(n)
        fadd = jnp.asarray(np.zeros((n,), np.float32) if fadd is None
                           else fadd)
        with span("repro.decode.dispatch", lanes=n):
            if np.all(np.asarray(temperature) <= 0.0):
                toks, ok, pool = self._decode_greedy_fn(
                    self.qp, pool, jnp.asarray(tokens), fadd)
            else:
                toks, ok, pool = self._decode_fn(
                    self.qp, pool, jnp.asarray(tokens),
                    jnp.asarray(temperature), jnp.asarray(top_k),
                    jnp.asarray(top_p), fadd)
        with span("repro.decode.readback"):
            self.last_ok = np.asarray(ok)
            toks = np.asarray(toks)
        return toks, pool

    def retry_step(self, pool, slot, tokens, temperature, top_k, top_p):
        """Recovery twin of :meth:`decode_step` for ONE quarantined lane
        (DESIGN.md §Fault-tolerance). Preconditions: the lane's faulted
        append was rewound bitwise (``rollback``), so its cache state is
        exactly pre-step. Recomputes the lane's token with the LOP screen
        disabled — exact dense attention, the degradation ladder's next
        rung — while the other lanes' state is frozen behind a masked
        active vector (their lengths, K/V and PRNG steps do not move).
        → (tokens [B] i32, ok [B] bool, pool); only row ``slot`` is
        meaningful. A sticky injected fault still poisons the retry —
        ``ok[slot]`` False means the lane is beyond recovery."""
        jnp = self._jnp
        n = np.asarray(tokens).shape[0]
        fadd = _faults.retry_fault_add(n)
        fadd = jnp.asarray(np.zeros((n,), np.float32) if fadd is None
                           else fadd)
        if np.all(np.asarray(temperature) <= 0.0):
            toks, ok, pool = self._retry_greedy_fn(
                self.qp, pool, jnp.int32(slot), jnp.asarray(tokens), fadd)
        else:
            toks, ok, pool = self._retry_fn(
                self.qp, pool, jnp.int32(slot), jnp.asarray(tokens),
                jnp.asarray(temperature), jnp.asarray(top_k),
                jnp.asarray(top_p), fadd)
        return np.asarray(toks), np.asarray(ok), pool

    # ---------------- speculative decoding ----------------

    def draft(self, pool, tokens, temperature, top_k, top_p):
        """One degraded-cost draft step over every active lane — the
        speculative proposer (truncated layer stack at ``draft_layers``,
        LOP selection pinched to ``draft_k`` blocks), batched like
        :meth:`decode_step` and sampled through the same in-pool PRNG
        schedule. Cache writes are provisional: verify overwrites them,
        :meth:`rollback` rewinds the rejected tail. → (tokens [B], pool).
        """
        jnp = self._jnp
        if np.all(np.asarray(temperature) <= 0.0):
            toks, pool = self._draft_greedy_fn(self.qp, pool,
                                               jnp.asarray(tokens))
        else:
            toks, pool = self._draft_fn(
                self.qp, pool, jnp.asarray(tokens),
                jnp.asarray(temperature), jnp.asarray(top_k),
                jnp.asarray(top_p))
        return np.asarray(toks), pool

    def verify_chunk(self, pool, slot, tokens, start):
        """Score γ+1 positions of lane ``slot`` exactly, in ONE
        chunk-shaped launch. ``tokens`` [1, γ+1] = [t_last, d_1..d_γ] at
        stream positions [start, start+γ+1); the full-stack K/V for all
        of them is (re)written through the bitwise ``(start, kv_len)``
        chunk carry — overwriting every provisional draft row — and the
        lane's length lands at ``start+γ+1``. Returns logits [1, γ+1, V]
        (row i targets position start+i+1) and the pool. Advances the
        lane's in-pool PRNG step by 1: with the γ draft advances and the
        ``rollback`` rewind of γ−j rejected tokens, a lane that accepts
        j drafts nets +j+1 — exactly its emission count. Compiles once
        per verify width."""
        key = ("verify", tokens.shape[1])
        fn = self._fns.get(key)
        if fn is None:
            cfg, use_lop = self.cfg, self.use_lop

            def verify_chunk(qp, pool_, slot_, toks, start_):
                lane = _cache.extract_slot(pool_, slot_)
                width = toks.shape[1]
                logits, lane = chunk_forward(
                    cfg, qp, toks, lane, start=start_,
                    seq_end=start_ + width, all_logits=True)
                pool_ = _cache.insert_slot(pool_, slot_, lane, active=True)
                pool_ = dict(pool_)
                pool_["sample_step"] = \
                    pool_["sample_step"].at[slot_].add(1)
                return logits, pool_

            fn = jax.jit(verify_chunk, donate_argnums=(1,))
            self._fns[key] = fn
            self.prefill_compiles += 1
        jnp = self._jnp
        logits, pool = fn(self.qp, pool, jnp.int32(slot),
                          jnp.asarray(tokens), jnp.int32(start))
        return np.asarray(logits), pool

    def rollback(self, pool, slot, n: int):
        """Rewind lane ``slot`` by ``n`` rejected speculative tokens —
        :func:`repro.serving.cache.rollback_slot` under jit (lengths −n,
        rejected K/V/scale/feature rows zeroed, PRNG step −n). One
        compile serves every (slot, n)."""
        jnp = self._jnp
        return self._rollback_fn(pool, jnp.int32(slot), jnp.int32(n))

    def sample_block(self, logits, sampling: SamplingParams,
                     first_step: int):
        """Sample every row of verify logits [C, V] (or [1, C, V]) under
        one request's policy — row i at PRNG step ``first_step + i``, the
        same per-emission key schedule the lane's decode steps use, so
        the accepted sampled stream is the non-speculative stream.
        → np.int32 [C]."""
        sp = sampling or GREEDY
        rows = np.asarray(logits)
        if rows.ndim == 3:
            rows = rows[0]
        c = rows.shape[0]
        toks = self._sample_fn(
            rows, np.full((c,), sp.seed, np.int32),
            first_step + np.arange(c, dtype=np.int32),
            np.full((c,), sp.temperature, np.float32),
            np.full((c,), sp.top_k, np.int32),
            np.full((c,), sp.top_p, np.float32))
        return np.asarray(toks)

    def evict(self, pool, slot):
        return self._evict_fn(pool, self._jnp.int32(slot))

    def set_sampling_state(self, pool, slot, seed: int, step: int):
        """Write lane ``slot``'s in-pool PRNG schedule (at activation:
        ``step=1`` — the prefill-seeded first token was emission 0,
        sampled host-side by :meth:`sample_first`)."""
        jnp = self._jnp
        return self._sampling_state_fn(pool, jnp.int32(slot),
                                       jnp.int32(seed), jnp.int32(step))

    def sample_first(self, logits, sampling: SamplingParams,
                     seed_step: int = 0) -> int:
        """Sample a request's first token from its prefill logits [B=1, V]
        through the SAME jitted sampler path as every later token (key
        schedule step ``seed_step``, normally 0)."""
        sp = sampling or GREEDY
        tok = self._sample_fn(
            logits[:1], np.asarray([sp.seed], np.int32),
            np.asarray([seed_step], np.int32),
            np.asarray([sp.temperature], np.float32),
            np.asarray([sp.top_k], np.int32),
            np.asarray([sp.top_p], np.float32))
        return int(tok[0])
