"""Fused prefill-attention kernel + chunked prefill: exactness pins.

Three layers of guarantees (DESIGN.md §Chunked-prefill):

  1. kernel vs oracle — the Pallas kernel and ``prefill_attention_ref``
     agree across GQA shapes, windows, cross (non-causal) masks, partial
     ``kv_len`` and ``q_offset``; a lane with ``kv_len == 0`` emits
     exactly zero.
  2. chunk-carry — splitting the query stream into chunks against the
     same capacity-padded cache is *bitwise* identical to one
     whole-prompt call, at the op level and through the full engine
     (``prefill_chunk`` chain vs ``prefill``).
  3. scheduling — the chunked-interleaved scheduler emits token-exact
     greedy output vs the run-to-completion scheduler and the lockstep
     reference, including prompts not divisible by the chunk size and
     the vlm image-prefix chunk.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune, ops
from repro.models.transformer import init_params
from repro.serving.cache import init_cache
from repro.serving.engine import prefill, prefill_chunk, serve_step
from repro.serving.quantize import quantize_params
from repro.serving.scheduler import Request, Scheduler, lockstep_generate

from tests.test_models_smoke import _reduced

MAX_LEN = 63          # pool capacity 64 with the reduced lop_block of 32


def _rand_inputs(rng, b, h, hkv, c, dh, m):
    qi = jnp.asarray(rng.integers(-127, 128, (b, h, c, dh)), jnp.int8)
    qsc = jnp.asarray(rng.random((b, h, c)) * 0.1 + 0.01, jnp.float32)
    ki = jnp.asarray(rng.integers(-127, 128, (b, hkv, m, dh)), jnp.int8)
    vi = jnp.asarray(rng.integers(-127, 128, (b, hkv, m, dh)), jnp.int8)
    ks = jnp.asarray(rng.random((b, hkv, m)) * 0.1 + 0.01, jnp.float32)
    vs = jnp.asarray(rng.random((b, hkv, m)) * 0.1 + 0.01, jnp.float32)
    return qi, qsc, ki, vi, ks, vs


@pytest.mark.parametrize("hkv,window,causal,int8_logits", [
    (4, 0, True, False),      # MHA causal
    (2, 0, True, False),      # GQA causal
    (2, 12, True, True),      # GQA + SWA window, integer-domain logits
    (2, 0, False, False),     # cross / encoder (non-causal, kv_len mask)
])
def test_prefill_kernel_vs_ref(hkv, window, causal, int8_logits):
    rng = np.random.default_rng(0)
    b, h, c, dh, m = 2, 4, 8, 32, 64
    qi, qsc, ki, vi, ks, vs = _rand_inputs(rng, b, h, hkv, c, dh, m)
    kv_len = jnp.asarray([40, 0], jnp.int32)   # lane 1 retired/empty
    kw = dict(q_offset=32, causal=causal, window=window,
              int8_logits=int8_logits)
    o_ref = ops.prefill_attention(qi, qsc, ki, vi, ks, vs, kv_len,
                                  impl="ref", **kw)
    o_ker = ops.prefill_attention(qi, qsc, ki, vi, ks, vs, kv_len,
                                  impl="pallas", **kw)
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)
    # empty lane emits exactly zero in both arms
    assert bool(jnp.all(o_ref[1] == 0)) and bool(jnp.all(o_ker[1] == 0))


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_int8_logits_is_bitwise_on_cpu(impl):
    """Both QKᵀ branches dequantize after the dot; int8 products summed
    in f32 are exact below 2²⁴, so the branches cannot knife-edge apart
    under repeated absmax requantization (the avalanche regression)."""
    rng = np.random.default_rng(1)
    qi, qsc, ki, vi, ks, vs = _rand_inputs(rng, 1, 4, 2, 8, 32, 64)
    kv_len = jnp.asarray([40], jnp.int32)
    a = ops.prefill_attention(qi, qsc, ki, vi, ks, vs, kv_len,
                              causal=True, int8_logits=False, impl=impl)
    b = ops.prefill_attention(qi, qsc, ki, vi, ks, vs, kv_len,
                              causal=True, int8_logits=True, impl=impl)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_chunked_rows_bitwise_equal_whole(impl):
    """Chunk-carry invariant at the op level: per query row, a chunked
    call folds the same tiles with the same masks, so chunked == whole
    BITWISE over the same capacity-padded cache."""
    rng = np.random.default_rng(2)
    b, h, hkv, c, dh, m = 1, 4, 2, 16, 32, 64
    qi, qsc, ki, vi, ks, vs = _rand_inputs(rng, b, h, hkv, c, dh, m)
    whole = ops.prefill_attention(qi, qsc, ki, vi, ks, vs,
                                  jnp.asarray([48], jnp.int32),
                                  q_offset=32, causal=True, impl=impl)
    parts = []
    for i in range(4):                     # 4 chunks of 4 query rows
        sl = slice(i * 4, (i + 1) * 4)
        parts.append(ops.prefill_attention(
            qi[:, :, sl], qsc[:, :, sl], ki, vi, ks, vs,
            jnp.asarray([32 + (i + 1) * 4], jnp.int32),
            q_offset=32 + i * 4, causal=True, impl=impl))
    np.testing.assert_array_equal(np.asarray(whole),
                                  np.asarray(jnp.concatenate(parts, 2)))


# ---------------------------------------------------------------------------
# Autotune tiling matrix (DESIGN.md §Autotuning)
# ---------------------------------------------------------------------------

PREFILL_TILINGS = [
    # (block, bq): kv-tile sweeps and query-row (third grid axis) tiles;
    # kv_len below is NOT a multiple of any of these blocks
    (16, 0),
    (64, 8),
    (32, 16),
]


@pytest.mark.parametrize("block,bq", PREFILL_TILINGS)
def test_prefill_tiling_matrix(block, bq):
    """Swept (block, bq) under autotune.override: allclose vs the ref
    oracle on ragged kv_len (not a multiple of the kv block), and every
    bq variant BITWISE vs the untiled launch at the same kv block."""
    rng = np.random.default_rng(11)
    b, h, hkv, c, dh, m = 2, 4, 2, 8, 32, 64
    r = (h // hkv) * c
    assert autotune.valid_params(
        "prefill", {"bhg": b * hkv, "r": r, "d": dh, "m": m, "chunk": c},
        {"block": block, "bq": bq})
    qi, qsc, ki, vi, ks, vs = _rand_inputs(rng, b, h, hkv, c, dh, m)
    kv_len = jnp.asarray([41, 33], jnp.int32)
    kw = dict(q_offset=25, causal=True, window=0)
    o_ref = ops.prefill_attention(qi, qsc, ki, vi, ks, vs, kv_len,
                                  impl="ref", **kw)
    with autotune.override("prefill", block=block, bq=bq):
        o_t = ops.prefill_attention(qi, qsc, ki, vi, ks, vs, kv_len,
                                    impl="pallas", **kw)
    np.testing.assert_allclose(np.asarray(o_t), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)
    with autotune.override("prefill", block=block, bq=0):
        o_b = ops.prefill_attention(qi, qsc, ki, vi, ks, vs, kv_len,
                                    impl="pallas", **kw)
    np.testing.assert_array_equal(np.asarray(o_t), np.asarray(o_b))


@pytest.mark.parametrize("bq", [1, 4])
def test_prefill_tiling_rejects_sub_tile_bq(bq):
    """A query-row tile below the 8-row sublane tile is not a block Mosaic
    takes: the screen refuses it, and an override falls back to the
    untiled launch."""
    dims = {"bhg": 4, "r": 16, "d": 32, "m": 64, "chunk": 8}
    assert not autotune.valid_params("prefill", dims,
                                     {"block": 32, "bq": bq})
    with autotune.override("prefill", block=32, bq=bq):
        assert autotune.lookup("prefill", dims) == {}


def _setup(arch="bitnet-3b", **over):
    cfg = _reduced(arch).replace(**over) if over else _reduced(arch)
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, quantize_params(cfg, params)


def test_engine_chunked_prefill_bitwise_equals_whole():
    """prefill_chunk chain == whole-prompt prefill: final logits, cache
    contents over the valid region, and the next decode step, bitwise."""
    cfg, qp = _setup()
    rng = np.random.default_rng(3)
    plen, c = 27, 16                       # 27 % 16 != 0 → padded tail
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (1, plen)), jnp.int32)
    logits_w, cache_w = prefill(cfg, qp, prompt, max_len=MAX_LEN)

    cache = init_cache(cfg, 1, MAX_LEN)
    for k in range(2):
        lo, hi = k * c, min(plen, (k + 1) * c)
        buf = np.zeros((1, c), np.int32)
        buf[0, :hi - lo] = np.asarray(prompt[0, lo:hi])
        logits, cache = prefill_chunk(cfg, qp, jnp.asarray(buf), cache,
                                      start=jnp.int32(lo),
                                      seq_end=jnp.int32(hi))
    np.testing.assert_array_equal(np.asarray(logits_w), np.asarray(logits))
    np.testing.assert_array_equal(
        np.asarray(cache_w["layers"]["k"][..., :plen, :]),
        np.asarray(cache["layers"]["k"][..., :plen, :]))
    d1, _ = serve_step(cfg, qp, cache_w, jnp.asarray([[7]], jnp.int32))
    d2, _ = serve_step(cfg, qp, cache, jnp.asarray([[7]], jnp.int32))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


def test_scheduler_chunked_matches_lockstep_at_chunk_boundaries():
    """Chunked-interleaved scheduling is token-exact vs the lockstep
    reference for prompts below / at / straddling chunk multiples, with
    ONE chunk-shape compile covering every prompt."""
    cfg, qp = _setup()
    rng = np.random.default_rng(4)
    lens = [9, 16, 17, 32, 33, 45]        # <C, ==C, C+1, 2C, 2C+1, ...
    prompts = [rng.integers(0, cfg.vocab, (n,)).astype(np.int32)
               for n in lens]
    sched = Scheduler(cfg, qp, n_slots=2, max_len=MAX_LEN, chunk_tokens=16)
    assert sched.chunked
    for rid, p in enumerate(prompts):
        sched.submit(Request(rid=rid, prompt=p, max_new_tokens=5))
    results = sched.run_to_completion()
    assert sched.prefill_compiles == 1    # one fixed chunk shape
    assert sched.interleaved_decode_steps > 0
    for rid, p in enumerate(prompts):
        got = next(r for r in results if r.rid == rid)
        ref = lockstep_generate(cfg, qp, p, 5, max_len=MAX_LEN)
        assert got.tokens == ref, (rid, got.tokens, ref)


def test_scheduler_chunked_matches_run_to_completion():
    """Interleaving is a pure scheduling change: same tokens as the
    legacy run-to-completion scheduler on the same traffic."""
    cfg, qp = _setup()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, (n,)).astype(np.int32)
               for n in [12, 40, 21]]

    def run(chunked):
        s = Scheduler(cfg, qp, n_slots=2, max_len=MAX_LEN, chunked=chunked)
        for rid, p in enumerate(prompts):
            s.submit(Request(rid=rid, prompt=p, max_new_tokens=6))
        return {r.rid: r.tokens for r in s.run_to_completion()}, s

    toks_c, sc = run(True)
    toks_l, sl = run(False)
    assert toks_c == toks_l
    assert sc.full_prefill_stalls == 0    # chunked never blocks a batch
    assert sl.full_prefill_stalls > 0     # legacy does (slots were busy)


def test_vlm_image_prefix_rides_first_chunk():
    """llava-style requests chunk the [patches ‖ text] stream; the first
    chunk carries the patch embeds and later chunks shift by the prefix."""
    cfg, qp = _setup("llava-next-34b")
    rng = np.random.default_rng(6)
    max_len = 60
    sched = Scheduler(cfg, qp, n_slots=2, max_len=max_len, chunk_tokens=16)
    assert sched.chunked
    reqs = []
    for rid, plen in enumerate([7, 19]):   # 19 → two chunks past prefix
        p = rng.integers(0, cfg.vocab, (plen,)).astype(np.int32)
        patches = (rng.standard_normal((cfg.n_img_tokens, cfg.d_model))
                   .astype(np.float32) * 0.02)
        reqs.append(Request(rid=rid, prompt=p, max_new_tokens=4,
                            patches=patches))
        sched.submit(reqs[-1])
    results = sched.run_to_completion()
    for req in reqs:
        got = next(r for r in results if r.rid == req.rid)
        ref = lockstep_generate(cfg, qp, req.prompt, 4, max_len=max_len,
                                patches=req.patches)
        assert got.tokens == ref, req.rid


def test_float_path_chunk_carry_matches_full_stream():
    """models/attention + transformer chunk-carry: a suffix chunk scored
    against the full stream equals the same rows of a full-stream call
    (the training/eval mirror of engine chunked prefill)."""
    from repro.models.attention import attention_apply
    from repro.models.transformer import decoder_layer_apply

    cfg = _reduced("bitnet-3b")
    params, _ = init_params(cfg, jax.random.PRNGKey(1))
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((2, 24, cfg.d_model)), jnp.float32)
    c = 8
    full = attention_apply(cfg, lp["attn"], x)
    part = attention_apply(cfg, lp["attn"], x[:, -c:], kv_x=x,
                           chunk_carry=True, q_offset=24 - c)
    np.testing.assert_allclose(np.asarray(full[:, -c:]), np.asarray(part),
                               rtol=1e-5, atol=1e-5)

    pos = jnp.arange(24)[None, :]
    yf, _ = decoder_layer_apply(cfg, lp, x, positions=pos)
    yc, _ = decoder_layer_apply(cfg, lp, x[:, -c:], positions=pos[:, -c:],
                                chunk_ctx=x)
    np.testing.assert_allclose(np.asarray(yf[:, -c:]), np.asarray(yc),
                               rtol=1e-5, atol=1e-5)
