"""Continuous-batching scheduler: lifecycle + lockstep token equivalence.

The core guarantee of the slot-paged engine: a request decodes the *same
greedy tokens* whether it shares the pool with other requests (staggered
arrivals, mixed prompt lengths, lane reuse) or runs alone through the
lockstep prefill+decode path at the same cache capacity.
"""
import jax
import numpy as np
import pytest

from repro.launch.serve import serve_loop
from repro.models.transformer import init_params
from repro.serving.quantize import quantize_params
from repro.serving.scheduler import (Request, Scheduler, lockstep_generate,
                                     pow2_bucket)

from tests.test_models_smoke import _reduced

MAX_LEN = 63          # pool capacity 64 with the reduced lop_block of 32


def _setup(arch="bitnet-3b", **over):
    cfg = _reduced(arch).replace(**over) if over else _reduced(arch)
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, quantize_params(cfg, params)


def _prompts(cfg, lens, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, (n,)).astype(np.int32) for n in lens]


def test_pow2_bucketing_bounds_compiles():
    assert pow2_bucket(3, lo=16) == 16
    assert pow2_bucket(16, lo=16) == 16
    assert pow2_bucket(17, lo=16) == 32
    assert pow2_bucket(100, lo=16, hi=63) == 63
    # every length in [1, 64] lands in one of 3 buckets
    assert {pow2_bucket(n, lo=16, hi=64) for n in range(1, 65)} == {16, 32,
                                                                    64}


def test_staggered_mixed_length_equals_lockstep():
    """Requests admitted into a live pool at different steps emit the same
    greedy tokens as solo lockstep runs (the acceptance criterion)."""
    cfg, qp = _setup()
    prompts = _prompts(cfg, [12, 27, 9, 33, 17])
    sched = Scheduler(cfg, qp, n_slots=2, max_len=MAX_LEN)
    for rid, p in enumerate(prompts):
        sched.submit(Request(rid=rid, prompt=p, max_new_tokens=6))
    results = sched.run_to_completion()
    assert len(results) == len(prompts)
    assert sched.prefill_compiles <= 3          # buckets, not lengths
    for rid, p in enumerate(prompts):
        got = next(r for r in results if r.rid == rid)
        ref = lockstep_generate(cfg, qp, p, 6, max_len=MAX_LEN)
        assert got.tokens == ref, (rid, got.tokens, ref)
        assert got.finish_reason == "length"


def test_lane_reuse_after_evict_matches_fresh():
    """A lane that served a long request is reused for a new one; stale
    bytes above the new length must not leak (same tokens as fresh run)."""
    cfg, qp = _setup()
    long_p, short_p = _prompts(cfg, [40, 8], seed=5)
    sched = Scheduler(cfg, qp, n_slots=1, max_len=MAX_LEN)
    sched.submit(Request(rid=0, prompt=long_p, max_new_tokens=8))
    sched.submit(Request(rid=1, prompt=short_p, max_new_tokens=8))
    results = sched.run_to_completion()
    reused = next(r for r in results if r.rid == 1)
    fresh = Scheduler(cfg, qp, n_slots=1, max_len=MAX_LEN)
    fresh.submit(Request(rid=1, prompt=short_p, max_new_tokens=8))
    assert reused.tokens == fresh.run_to_completion()[0].tokens


def test_eos_early_exit_frees_lane():
    cfg, qp = _setup()
    (p,) = _prompts(cfg, [10])
    ref = lockstep_generate(cfg, qp, p, 12, max_len=MAX_LEN)
    eos = ref[3]                                 # force an early EOS hit
    sched = Scheduler(cfg, qp, n_slots=1, max_len=MAX_LEN)
    sched.submit(Request(rid=0, prompt=p, max_new_tokens=12, eos_id=eos))
    res = sched.run_to_completion()[0]
    assert res.finish_reason == "eos"
    assert res.tokens == ref[:res.tokens.index(eos) + 1]
    assert sched.n_active == 0 and len(sched.queue) == 0


def test_first_token_eos_finishes_lane_immediately():
    """Regression (ISSUE 5 satellite): the first-token EOS predicate was
    evaluated twice in ``_start_lane`` to pick the finish reason; the
    single-evaluation rewrite must still finish a request whose FIRST
    sampled token is EOS with reason "eos", exactly one token, and an
    immediately reusable lane — through the chunked-prefill activation
    path (``_start_lane`` called from ``_step_prefill``)."""
    cfg, qp = _setup()
    (p,) = _prompts(cfg, [10])
    ref = lockstep_generate(cfg, qp, p, 4, max_len=MAX_LEN)
    sched = Scheduler(cfg, qp, n_slots=1, max_len=MAX_LEN)
    assert sched.chunked
    sched.submit(Request(rid=0, prompt=p, max_new_tokens=4, eos_id=ref[0]))
    res = sched.run_to_completion()[0]
    assert res.finish_reason == "eos"
    assert res.tokens == [ref[0]]
    assert sched.n_active == 0 and len(sched._free) == 1


def test_max_new_tokens_one_edge_cases():
    """max_new_tokens=1: the budget is spent on the prefill-seeded first
    token — reason "length" when it is not EOS, "eos" (taking precedence)
    when it is; both through the pooled path and the lockstep
    reference."""
    cfg, qp = _setup()
    (p,) = _prompts(cfg, [12], seed=17)
    ref = lockstep_generate(cfg, qp, p, 1, max_len=MAX_LEN)
    assert len(ref) == 1

    sched = Scheduler(cfg, qp, n_slots=1, max_len=MAX_LEN)
    sched.submit(Request(rid=0, prompt=p, max_new_tokens=1))
    res = sched.run_to_completion()[0]
    assert res.tokens == ref and res.finish_reason == "length"

    sched2 = Scheduler(cfg, qp, n_slots=1, max_len=MAX_LEN)
    sched2.submit(Request(rid=0, prompt=p, max_new_tokens=1,
                          eos_id=ref[0]))
    res2 = sched2.run_to_completion()[0]
    assert res2.tokens == ref and res2.finish_reason == "eos"
    # the lockstep reference stops at the same single token either way
    assert lockstep_generate(cfg, qp, p, 1, max_len=MAX_LEN,
                             eos_id=ref[0]) == ref


def test_capacity_guard_rejects_oversized_request():
    cfg, qp = _setup()
    (p,) = _prompts(cfg, [60])
    sched = Scheduler(cfg, qp, n_slots=1, max_len=MAX_LEN)
    with pytest.raises(AssertionError):
        sched.submit(Request(rid=0, prompt=p, max_new_tokens=30))


def test_moe_exact_length_prefill_matches_lockstep():
    """MoE serves dropless experts (every expert's bucket holds every row
    of the call), so a token's output depends on its own row alone: moe
    prefills in fixed-size chunks like dense — no exact-length or pow2
    compiles — interns prefix pages, and a prefix hit still decodes
    token-identically to the unpadded whole-prompt lockstep reference.
    17 fits one 32-token chunk, 40 takes two, and the last prompt shares
    its first 32-token block with the 40-token one."""
    cfg, qp = _setup("granite-moe-1b-a400m")
    base = _prompts(cfg, [17, 23, 40], seed=11)
    prompts = base + [np.concatenate([base[2][:32], _prompts(
        cfg, [9], seed=12)[0]])]
    sched = Scheduler(cfg, qp, n_slots=2, max_len=MAX_LEN)
    assert sched.chunked and sched.prefix_store is not None
    results = []
    for batch in (range(3), [3]):                # the hit comes after
        for rid in batch:
            sched.submit(Request(rid=rid, prompt=prompts[rid],
                                 max_new_tokens=5))
        results += sched.run_to_completion()
    assert sched.prefill_compiles == 1           # one chunk shape
    assert sched.prefix_store.hits >= 1
    for rid, p in enumerate(prompts):
        got = next(r for r in results if r.rid == rid)
        ref = lockstep_generate(cfg, qp, p, 5, max_len=MAX_LEN)
        assert got.tokens == ref, (rid, got.tokens, ref)
    assert next(r for r in results if r.rid == 3).cached_len == 32


def test_recurrent_family_uses_exact_length_prefill():
    """rwkv6 state integrates every position — the scheduler must not pad
    its prompts, and pooled decode must still match the solo path."""
    cfg, qp = _setup("rwkv6-1.6b")
    prompts = _prompts(cfg, [11, 19], seed=7)
    sched = Scheduler(cfg, qp, n_slots=2, max_len=MAX_LEN)
    for rid, p in enumerate(prompts):
        sched.submit(Request(rid=rid, prompt=p, max_new_tokens=5))
    results = sched.run_to_completion()
    assert sched.prefill_compiles == 2           # one per distinct length
    for rid, p in enumerate(prompts):
        got = next(r for r in results if r.rid == rid)
        assert got.tokens == lockstep_generate(cfg, qp, p, 5,
                                               max_len=MAX_LEN)


def test_encdec_requests_carry_frames():
    """Whisper-style requests travel with their encoder frames and still
    match the solo lockstep run (regression: the first driver rewrite
    dropped frames/patches support)."""
    cfg, qp = _setup("whisper-small")
    rng = np.random.default_rng(9)
    sched = Scheduler(cfg, qp, n_slots=2, max_len=40)
    reqs = []
    for rid, plen in enumerate([6, 9]):
        p = rng.integers(0, cfg.vocab, (plen,)).astype(np.int32)
        f = rng.standard_normal((4 * plen, cfg.d_model)).astype(
            np.float32) * 0.02
        reqs.append(Request(rid=rid, prompt=p, max_new_tokens=4, frames=f))
        sched.submit(reqs[-1])
    results = sched.run_to_completion()
    for req in reqs:
        got = next(r for r in results if r.rid == req.rid)
        ref = lockstep_generate(cfg, qp, req.prompt, 4, max_len=40,
                                frames=req.frames)
        assert got.tokens == ref, req.rid
    # oversized encoder input is rejected up front, not at insert time
    with pytest.raises(AssertionError):
        sched.submit(Request(rid=9, prompt=reqs[0].prompt, max_new_tokens=2,
                             frames=np.zeros((cfg.cross_ctx + 33,
                                              cfg.d_model), np.float32)))


@pytest.mark.slow
def test_serve_loop_driver_reports_latency_and_verifies():
    cfg = _reduced("bitnet-3b")
    out = serve_loop(cfg, n_slots=2, n_requests=4, min_prompt=6,
                     max_prompt=20, gen=5, verify=True)
    assert out["verified"], out["mismatched_rids"]
    assert len(out["results"]) == 4
    assert out["tokens_per_s"] > 0
    assert out["latency_p99"] >= out["latency_p50"] > 0
    assert all(r.ttft <= r.latency for r in out["results"])
