"""Serving engine: prefill/decode consistency, LOP exactness, generation,
slot-paged cache pool semantics."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.transformer import init_params
from repro.serving.cache import (cache_pspecs, evict_slot, free_slots,
                                 init_cache, init_cache_pool, insert_slot,
                                 pool_capacity)
from repro.serving.engine import prefill, serve_step
from repro.serving.quantize import quantize_params

from tests.test_models_smoke import ARCH_MODULES, _reduced

CONSISTENCY_ARCHS = ["mixtral-8x22b", "whisper-small",
                     "jamba-1.5-large-398b", "llava-next-34b",
                     "rwkv6-1.6b", "bitnet-3b"]


def _inputs(cfg, b, s, key=1):
    rng = np.random.default_rng(key)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32)
    kw = {}
    if cfg.family == "encdec":
        kw["frames"] = jnp.asarray(rng.standard_normal((b, 48, cfg.d_model)),
                                   jnp.float32) * 0.05
    if cfg.family == "vlm":
        kw["patches"] = jnp.asarray(
            rng.standard_normal((b, cfg.n_img_tokens, cfg.d_model)),
            jnp.float32) * 0.05
    return tokens, kw


@pytest.mark.parametrize("arch", CONSISTENCY_ARCHS)
def test_prefill_plus_decode_equals_full_prefill(arch):
    """With lop_keep=1.0 the sparse decode path is exact: prefill(S) +
    serve_step == prefill(S+1) (the paper's no-retraining guarantee at
    K=M)."""
    cfg = _reduced(arch).replace(lop_keep=1.0, capacity_factor=8.0)
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    qp = quantize_params(cfg, params)
    B, S = 2, 20
    tokens, kw = _inputs(cfg, B, S + 1)

    logits_full, _ = prefill(cfg, qp, tokens, max_len=S + 2, **kw)
    _, cache = prefill(cfg, qp, tokens[:, :S], max_len=S + 2, **kw)
    logits_dec, cache2 = serve_step(cfg, qp, cache, tokens[:, S:S + 1])

    ref = float(jnp.max(jnp.abs(logits_full))) + 1e-9
    err = float(jnp.max(jnp.abs(logits_dec - logits_full)))
    assert err / ref < 2e-2, (arch, err, ref)
    assert np.isfinite(np.asarray(logits_dec)).all()


def test_sparse_decode_finite_and_close():
    cfg = _reduced("bitnet-3b").replace(lop_keep=0.5)
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    qp = quantize_params(cfg, params)
    tokens, kw = _inputs(cfg, 2, 21)
    logits_full, _ = prefill(cfg, qp, tokens, max_len=24, **kw)
    _, cache = prefill(cfg, qp, tokens[:, :20], max_len=24, **kw)
    logits_sp, _ = serve_step(cfg, qp, cache, tokens[:, 20:21])
    rel = float(jnp.linalg.norm(logits_sp - logits_full)
                / (jnp.linalg.norm(logits_full) + 1e-9))
    assert np.isfinite(np.asarray(logits_sp)).all()
    assert rel < 0.5, rel


def test_greedy_generation_deterministic():
    cfg = _reduced("stablelm-1.6b")
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    qp = quantize_params(cfg, params)
    tokens, _ = _inputs(cfg, 2, 8)

    def gen():
        logits, cache = prefill(cfg, qp, tokens, max_len=8 + 8)
        out = []
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        for _ in range(8):
            out.append(np.asarray(tok))
            logits, cache = serve_step(cfg, qp, cache, tok)
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        return np.concatenate(out, 1)

    a, b = gen(), gen()
    assert (a == b).all()
    assert (a >= 0).all() and (a < cfg.vocab_padded).all()


@pytest.mark.parametrize("n", [4, 11])
def test_logits_rows_independent_of_batch(n):
    """A row's logits do not depend on how many rows share the head call:
    a pool lane's greedy token must equal the batch-1 lockstep path's,
    bitwise. The head runs on fixed 8-row tiles; an untiled f32 matmul
    changes algorithm with the row count (on the CPU and on the TPU)."""
    from repro.serving.engine import _logits

    cfg = _reduced("bitnet-3b")
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    qp = quantize_params(cfg, params)
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (n, cfg.d_model)), jnp.float32)
    logits = jax.jit(lambda x: _logits(cfg, qp, x))
    batched = np.asarray(logits(x))
    assert batched.shape == (n, cfg.vocab_padded)
    for i in range(n):
        assert (np.asarray(logits(x[i:i + 1]))[0] == batched[i]).all(), i


def test_swa_window_limits_decode_attention():
    """Mixtral SWA: tokens beyond the (depth-stacked) receptive field must
    not affect decode. With 1 layer + window W, the decode step at position
    S sees K/V from [S−W, S), which themselves depend on tokens ≥ S−2W."""
    cfg = _reduced("mixtral-8x22b").replace(lop_keep=1.0, swa_window=16,
                                            capacity_factor=8.0, n_layers=1)
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    qp = quantize_params(cfg, params)
    S = 40
    cut = S - 2 * cfg.swa_window  # = 8: outside the receptive field
    tokens, _ = _inputs(cfg, 1, S + 1)
    tok2 = tokens.at[:, :cut].set((tokens[:, :cut] + 1) % cfg.vocab)
    _, c1 = prefill(cfg, qp, tokens[:, :S], max_len=S + 2)
    _, c2 = prefill(cfg, qp, tok2[:, :S], max_len=S + 2)
    l1, _ = serve_step(cfg, qp, c1, tokens[:, S:S + 1])
    l2, _ = serve_step(cfg, qp, c2, tokens[:, S:S + 1])
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-4)
    # sanity: in-window changes DO affect the logits
    tok3 = tokens.at[:, S - 4].set((tokens[:, S - 4] + 1) % cfg.vocab)
    _, c3 = prefill(cfg, qp, tok3[:, :S], max_len=S + 2)
    l3, _ = serve_step(cfg, qp, c3, tokens[:, S:S + 1])
    assert float(jnp.max(jnp.abs(l3 - l1))) > 1e-3


def test_init_cache_shapes():
    cfg = _reduced("jamba-1.5-large-398b")
    cache = init_cache(cfg, 2, 100)
    n_sb = cfg.n_layers // cfg.attn_every
    cap = -(-101 // cfg.lop_block) * cfg.lop_block
    assert cache["blocks"]["attn"]["k"].shape == (
        n_sb, 2, cfg.n_kv_heads, cap, cfg.hd)
    assert cache["blocks"]["mamba"]["ssm"].shape == (
        n_sb, cfg.attn_every - 1, 2, cfg.d_inner, cfg.mamba_d_state)
    assert cache["blocks"]["attn"]["feat"].shape[-1] == cfg.hd // 2


# ---------------------------------------------------------------------------
# Slot-paged pool
# ---------------------------------------------------------------------------

MAX_LEN = 63          # capacity 64 with the reduced lop_block of 32


def _pool_setup(arch="bitnet-3b", **over):
    cfg = _reduced(arch)
    if over:
        cfg = cfg.replace(**over)
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, quantize_params(cfg, params)


def _solo_tokens(cfg, qp, prompt, gen, use_lop=True):
    logits, cache = prefill(cfg, qp, prompt[None], max_len=MAX_LEN,
                            use_lop=use_lop)
    toks = [int(jnp.argmax(logits[0]))]
    for _ in range(gen):
        tok = jnp.asarray([[toks[-1]]], jnp.int32)
        logits, cache = serve_step(cfg, qp, cache, tok, use_lop=use_lop)
        toks.append(int(jnp.argmax(logits[0])))
    return toks


def _pool_decode(cfg, qp, pool, first_toks, gen, use_lop=True):
    """Greedy-decode every active lane of ``pool`` together."""
    n = pool["lengths"].shape[0]
    tok = np.zeros((n, 1), np.int32)
    out = {s: [t] for s, t in first_toks.items()}
    for s, t in first_toks.items():
        tok[s, 0] = t
    for _ in range(gen):
        logits, pool = serve_step(cfg, qp, pool, jnp.asarray(tok),
                                  use_lop=use_lop)
        for s in out:
            t = int(jnp.argmax(logits[s]))
            out[s].append(t)
            tok[s, 0] = t
    return out, pool


def test_variable_length_pool_matches_per_request_lockstep():
    """Lanes with different lengths decode together exactly as each request
    does alone — the slot-paged engine's core invariant."""
    cfg, qp = _pool_setup()
    rng = np.random.default_rng(11)
    prompts = {0: rng.integers(0, cfg.vocab, (13,)).astype(np.int32),
               2: rng.integers(0, cfg.vocab, (29,)).astype(np.int32)}
    pool = init_cache_pool(cfg, 3, MAX_LEN)          # lane 1 stays empty
    first = {}
    for slot, p in prompts.items():
        logits, req_cache = prefill(cfg, qp, p[None], max_len=MAX_LEN)
        pool = insert_slot(pool, jnp.int32(slot), req_cache)
        first[slot] = int(jnp.argmax(logits[0]))
    assert free_slots(pool) == [1]
    out, pool = _pool_decode(cfg, qp, pool, first, gen=6)
    np.testing.assert_array_equal(np.asarray(pool["lengths"]),
                                  [13 + 6, 0, 29 + 6])
    for slot, p in prompts.items():
        assert out[slot] == _solo_tokens(cfg, qp, p, 6), slot


def test_evict_insert_reuse_matches_fresh_cache():
    cfg, qp = _pool_setup()
    rng = np.random.default_rng(12)
    a = rng.integers(0, cfg.vocab, (45,)).astype(np.int32)
    b = rng.integers(0, cfg.vocab, (10,)).astype(np.int32)
    pool = init_cache_pool(cfg, 2, MAX_LEN)
    la, ca = prefill(cfg, qp, a[None], max_len=MAX_LEN)
    pool = insert_slot(pool, jnp.int32(0), ca)
    out, pool = _pool_decode(cfg, qp, pool,
                             {0: int(jnp.argmax(la[0]))}, gen=5)
    pool = evict_slot(pool, jnp.int32(0))
    assert free_slots(pool) == [0, 1]
    lb, cb = prefill(cfg, qp, b[None], max_len=MAX_LEN)
    pool = insert_slot(pool, jnp.int32(0), cb)
    reused, _ = _pool_decode(cfg, qp, pool,
                             {0: int(jnp.argmax(lb[0]))}, gen=5)
    fresh_pool = insert_slot(init_cache_pool(cfg, 2, MAX_LEN),
                             jnp.int32(0), cb)
    fresh, _ = _pool_decode(cfg, qp, fresh_pool,
                            {0: int(jnp.argmax(lb[0]))}, gen=5)
    assert reused[0] == fresh[0]


def test_slot_paged_lop_agrees_with_dense_at_full_keep():
    """use_lop=True at keep=1.0 must match the dense baseline on the
    slot-paged path (the paper's K=M exactness, now with masked lanes)."""
    cfg, qp = _pool_setup(lop_keep=1.0)
    rng = np.random.default_rng(13)
    p = rng.integers(0, cfg.vocab, (21,)).astype(np.int32)

    def run(use_lop):
        pool = init_cache_pool(cfg, 2, MAX_LEN)
        logits, rc = prefill(cfg, qp, p[None], max_len=MAX_LEN,
                             use_lop=use_lop)
        pool = insert_slot(pool, jnp.int32(1), rc)
        tok = np.zeros((2, 1), np.int32)
        tok[1, 0] = int(jnp.argmax(logits[0]))
        logits2, _ = serve_step(cfg, qp, pool, jnp.asarray(tok),
                                use_lop=use_lop)
        return logits2[1]

    lop, dense = run(True), run(False)
    ref = float(jnp.max(jnp.abs(dense))) + 1e-9
    err = float(jnp.max(jnp.abs(lop - dense)))
    assert err / ref < 2e-2, (err, ref)


def test_pool_tree_matches_lockstep_cache_plus_active():
    """The pool is init_cache + per-lane active (so serve_step, cache_pspecs
    and the dryrun cells all keep working), and insert sets length/active."""
    for arch in ("jamba-1.5-large-398b", "whisper-small", "rwkv6-1.6b"):
        cfg = _reduced(arch)
        pool = init_cache_pool(cfg, 2, 60)
        base = init_cache(cfg, 2, 60)
        assert set(pool) == set(base) | {"active", "seed", "sample_step"}
        assert not np.asarray(pool["active"]).any()
        specs = cache_pspecs(cfg, pool)
        assert specs["active"] == (None,)
        assert specs["seed"] == (None,)
        assert specs["sample_step"] == (None,)
        if cfg.family != "ssm":
            assert pool_capacity(pool) > 0


def test_inactive_lanes_do_not_drift():
    """Decoding with every lane inactive must leave lengths untouched and
    produce finite logits (masked screen/top-K/write paths)."""
    cfg, qp = _pool_setup()
    pool = init_cache_pool(cfg, 2, MAX_LEN)
    before = jax.tree.map(np.asarray, pool)
    logits, after = serve_step(cfg, qp, pool,
                               jnp.zeros((2, 1), jnp.int32))
    assert np.isfinite(np.asarray(logits)).all()
    np.testing.assert_array_equal(np.asarray(after["lengths"]),
                                  before["lengths"])
    np.testing.assert_array_equal(np.asarray(after["active"]),
                                  before["active"])
    for la, lb in zip(jax.tree.leaves(jax.tree.map(np.asarray, after)),
                      jax.tree.leaves(before)):
        np.testing.assert_array_equal(la, lb)


def test_extract_insert_round_trip_bit_exact():
    """Property test: extract_slot → partial insert_slot(active=) is a
    bit-exact round trip for KV pages, scales, packed LOP feature rows
    AND lengths, at lengths straddling block boundaries — the invariant
    bulk_insert (prefix cloning) relies on."""
    from repro.serving.cache import extract_slot

    cfg, qp = _pool_setup()
    rng = np.random.default_rng(21)
    # lengths below / at / above the lop_block=32 boundary
    for plen, active in [(13, True), (32, False), (33, True), (45, False)]:
        p = rng.integers(0, cfg.vocab, (plen,)).astype(np.int32)
        pool = init_cache_pool(cfg, 3, MAX_LEN)
        _, rc = prefill(cfg, qp, p[None], max_len=MAX_LEN)
        pool = insert_slot(pool, jnp.int32(1), rc, active=active)
        before = jax.tree.map(np.asarray, pool)
        lane = extract_slot(pool, jnp.int32(1))
        assert int(lane["lengths"][0]) == plen
        again = insert_slot(pool, jnp.int32(1), lane, active=active)
        after = jax.tree.map(np.asarray, again)
        for la, lb in zip(jax.tree.leaves(after), jax.tree.leaves(before)):
            np.testing.assert_array_equal(la, lb)


def test_evict_zeroes_lop_feature_rows():
    """Regression: evict_slot must zero the lane's packed LOP feature rows
    (not just lengths/active) so a later prefix-clone into the lane
    screens against exactly what a fresh pool would — no ghost features
    from the previous occupant."""
    cfg, qp = _pool_setup()
    rng = np.random.default_rng(22)
    p = rng.integers(0, cfg.vocab, (40,)).astype(np.int32)
    pool = init_cache_pool(cfg, 2, MAX_LEN)
    fresh_feat = np.asarray(pool["layers"]["feat"])
    la, rc = prefill(cfg, qp, p[None], max_len=MAX_LEN)
    pool = insert_slot(pool, jnp.int32(0), rc)
    out, pool = _pool_decode(cfg, qp, pool,
                             {0: int(jnp.argmax(la[0]))}, gen=3)
    assert np.asarray(pool["layers"]["feat"][:, 0]).any()
    pool = evict_slot(pool, jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(pool["layers"]["feat"]),
                                  fresh_feat)
    # K/V bytes may stay stale — only the feature rows must reset
    assert int(pool["lengths"][0]) == 0 and not bool(pool["active"][0])


def test_quantize_params_packs_linears():
    cfg = _reduced("bitnet-3b")
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    qp = quantize_params(cfg, params)
    # QKV fuses into ONE packed weight with a per-column γ row
    wqkv = qp["layers"]["attn"]["wqkv"]
    assert "packed" in wqkv and wqkv["packed"].dtype == jnp.uint8
    # packed is 4x smaller on the reduction dim
    assert wqkv["packed"].shape[-2] * 4 == params["layers"]["attn"]["wq"][
        "w"].shape[-2]
    assert wqkv["packed"].shape[-1] == cfg.q_dim + 2 * cfg.kv_dim
    assert wqkv["scale"].shape[-2:] == (1, cfg.q_dim + 2 * cfg.kv_dim)
    # the FFN becomes one whole-FFN node (gate‖up stream + down stream)
    ffn = qp["layers"]["ffn"]
    assert ffn["gu_packed"].shape[-1] == 2 * cfg.d_ff
    assert ffn["down_packed"].shape[-2] * 4 == cfg.d_ff
    # head/embed stay fp
    assert "w" in qp["head"] and "table" in qp["embed"]
    # fuse=False keeps the legacy one-node-per-projection format
    qp_legacy = quantize_params(cfg, params, fuse=False)
    attn = qp_legacy["layers"]["attn"]["wq"]
    assert "packed" in attn and attn["scale"].shape[-2:] == (1, 1)
    # bf16 config keeps everything fp
    qp_fp = quantize_params(cfg.replace(quant="bf16"), params)
    assert "w" in qp_fp["layers"]["attn"]["wq"]
