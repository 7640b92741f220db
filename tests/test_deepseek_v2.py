"""DeepSeek-V2's block on the served path, at a tiny size on the CPU.

Latent attention (MLA) over one int8 latent row a token, in the absorbed
form; YaRN rotary; a leading dense layer; dropless routed experts beside
shared ones. The served path — ``PooledEngine``/``Scheduler``, chunked
prefill over several chunks, then decode through the latent cache — is
held against the benchmark's plain float32 reference
(``bench/models/deepseek_v2.py``) on the same seeded random weights,
and the reference computed in fp8 (the control) must fail the same
tolerance.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.models import deepseek_v2 as M
from bench.reference import model as R
from repro.configs.deepseek_v2_lite import CONFIG, REDUCED
from repro.models.attention import mla_attend
from repro.models.layers import mla_softmax_scale, rope_freqs, yarn_freqs
from repro.models.moe import moe_serve
from repro.serving.api import PooledEngine
from repro.serving.engine import serve_step
from repro.serving.scheduler import Request, Scheduler, lockstep_generate

SEED = 2**33 + 17
MAX_LEN = 127                       # capacity 128: 4 blocks of 32
CHUNK = 32
# d 64, 4 heads; kv_lora_rank 32, rope 8, nope 16, v 16; 8 experts top-2
# of width 32 plus 1 shared; the first of 3 layers dense; vocab 256.
# lop_keep 1.0 keeps every block, as the chip cell's contexts do.
ARCH = dict(
    family="moe", n_layers=3, d_model=64, n_heads=4, n_kv_heads=1,
    head_dim=24, d_ff=128, vocab=256, n_experts=8, top_k=2, d_expert=32,
    n_shared_experts=1, first_k_dense=1, norm_topk_prob=False,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, rope_theta=10000.0, rope_factor=40.0,
    rope_original_max_pos=64, rope_beta_fast=32.0, rope_beta_slow=1.0,
    rope_mscale=0.707, rope_mscale_all_dim=0.707, norm="rmsnorm",
    gated_ffn=True, quant="ternary", lop_block=32, lop_keep=1.0)
CFG = M.model_config({"name": "deepseek-v2-tiny", "arch": ARCH})

# Served logits against the reference's, over every position compared.
# The program rounds where the reference does not (the absorbed form's
# int8 barriers on q_nope, on q~ and on the weighted latent), so at
# width 64 a rounding flip moves a logit by up to ~0.35 and the RMS
# error reads 0.019-0.056 over three seeds; the fp8 control reads RMS
# 0.13-0.20 and max 0.51-0.83 on the same positions (my CPU runs).
RMS_TOL = 0.09
MAX_TOL = 0.5


@pytest.fixture(scope="module")
def qp():
    return M.served_params(ARCH, SEED)


def _prompts(lens, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, ARCH["vocab"], (n,)).astype(np.int32)
            for n in lens]


def _reference(seq, n_prompt, n_rows, control=False):
    kk = R.k_keep_blocks(ARCH, R.capacity_of(ARCH, MAX_LEN))
    return M.logits(ARCH, SEED, seq, n_prompt, length=MAX_LEN + 1,
                    k_keep=kk, n_rows=n_rows, control=control)


def test_yarn_matches_published_formulas():
    """YaRN's frequencies and the softmax scale, against a direct
    transcription of DeepSeek-V2's published modelling code, at the
    published settings."""
    dim, base, factor = 64, 10000.0, 40.0
    orig, fast, slow = 4096, 32, 1

    def find_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) \
            / (2 * math.log(base))

    low = max(math.floor(find_dim(fast)), 0)
    high = min(math.ceil(find_dim(slow)), dim - 1)
    exps = np.arange(0, dim, 2, dtype=np.float32) / dim
    freq_extra = 1.0 / (base ** exps)
    freq_inter = 1.0 / (factor * base ** exps)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    want = freq_inter * (1 - mask) + freq_extra * mask
    np.testing.assert_array_equal(
        yarn_freqs(dim, base, factor, orig, fast, slow), want)
    np.testing.assert_array_equal(np.asarray(rope_freqs(CONFIG)), want)
    conf_arch = dict(ARCH, qk_rope_head_dim=64, rope_original_max_pos=4096,
                     qk_nope_head_dim=128)
    np.testing.assert_array_equal(M.yarn_inv_freq(conf_arch), want)
    m = 0.1 * 0.707 * math.log(40) + 1.0
    assert mla_softmax_scale(CONFIG) == pytest.approx(192 ** -0.5 * m * m)
    assert mla_softmax_scale(CONFIG) == pytest.approx(0.11472, abs=1e-5)
    assert M.softmax_scale(conf_arch) == pytest.approx(
        mla_softmax_scale(CONFIG))


def test_absorbed_attention_equals_published_form():
    """In float32, scoring the latent rows with the absorbed query
    (q_nope·W_UKᵀ ‖ q_rope) and mapping the weighted latent through W_UV
    equals the published form (k_nope, v = kv_b(c)) to 1e-5."""
    rng = np.random.default_rng(0)
    b, s, h, dn, dr, dc, dv = 2, 12, 4, 16, 8, 32, 16
    f = lambda *sh: jnp.asarray(rng.standard_normal(sh), jnp.float32)
    q_nope, q_rope = f(b, s, h, dn), f(b, s, h, dr)
    c, k_rope = f(b, s, dc), f(b, s, dr)
    w_kb = f(dc, h * (dn + dv)) / np.sqrt(dc)
    scale = 0.11
    with jax.default_matmul_precision("highest"):
        want = mla_attend(q_nope, q_rope, c, k_rope, w_kb,
                          softmax_scale=scale)
        w = w_kb.reshape(dc, h, dn + dv)
        w_uk, w_uv = w[..., :dn], w[..., dn:]
        q_abs = jnp.einsum("bshd,chd->bshc", q_nope, w_uk)
        lat = jnp.concatenate([c, k_rope], -1)
        s_ = jnp.einsum("bshc,btc->bhst",
                        jnp.concatenate([q_abs, q_rope], -1), lat) * scale
        s_ = jnp.where(jnp.tril(jnp.ones((s, s), bool)), s_, -1e30)
        o_lat = jnp.einsum("bhst,btc->bshc", jax.nn.softmax(s_, -1), c)
        got = jnp.einsum("bshc,chd->bshd", o_lat, w_uv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_dropless_moe_is_row_invariant(qp):
    """A token's MoE output is bitwise the same alone, in a batch of 8
    and across chunk splits of a prompt; with every token routed to the
    same two experts (a bucket far over any capacity factor) none is
    dropped: each equals its output alone."""
    p = jax.tree.map(lambda a: a[0], qp["layers"]["moe"])
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((1, 40, ARCH["d_model"])),
                    jnp.float32)
    run = jax.jit(lambda p, x: moe_serve(CFG, p, x))
    whole = np.asarray(run(p, x))
    alone = np.concatenate([np.asarray(run(p, x[:, i:i + 1]))
                            for i in range(40)], 1)
    eights = np.concatenate([np.asarray(run(p, x[:, i:i + 8]))
                             for i in range(0, 40, 8)], 1)
    chunks = np.concatenate([np.asarray(run(p, x[:, :17])),
                             np.asarray(run(p, x[:, 17:]))], 1)
    for other in (alone, eights, chunks):
        np.testing.assert_array_equal(whole, other)

    skewed = dict(p, router=p["router"].at[:, :2].add(100.0))
    crowd = np.asarray(run(skewed, x))
    solo = np.concatenate([np.asarray(run(skewed, x[:, i:i + 1]))
                           for i in range(40)], 1)
    np.testing.assert_array_equal(crowd, solo)


def test_served_weights_match_program_format():
    """The benchmark's served tree has the layout the program's own
    ``quantize_params`` gives a DeepSeek-style model."""
    from repro.models.transformer import init_params
    from repro.serving.quantize import quantize_params
    program = quantize_params(REDUCED, init_params(
        REDUCED, jax.random.PRNGKey(0))[0])
    arch = {k: getattr(REDUCED, k) for k in ARCH}
    arch["norm_topk_prob"] = False
    bench = jax.eval_shape(lambda: M.served_params(arch, SEED))
    shape = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)
    assert shape(jax.eval_shape(lambda: program)) == shape(bench)


def _chunked(eng, pool, slot, prompt):
    """Chunked prefill of ``prompt`` into lane ``slot`` → (logits, pool)."""
    n = len(prompt)
    for st in range(0, n, CHUNK):
        toks = np.zeros((1, CHUNK), np.int32)
        seg = prompt[st:st + CHUNK]
        toks[0, :len(seg)] = seg
        lg, pool = eng.prefill_chunk(pool, slot, toks, st,
                                     min(st + CHUNK, n), st + CHUNK >= n,
                                     {})
    return np.asarray(lg)[0], pool


def test_served_logits_match_reference(qp):
    """Chunked prefill (2 and 3 chunks) then 8 decode steps through the
    latent cache, two lanes decoding together: every logit row against
    the plain reference's, within RMS_TOL and MAX_TOL; the fp8 control
    breaks them."""
    eng = PooledEngine(CFG, qp, max_len=MAX_LEN, chunk_tokens=CHUNK)
    prompts = _prompts([45, 70])
    pool = eng.init_pool(2)
    rows = []
    for slot, p in enumerate(prompts):
        lg, pool = _chunked(eng, pool, slot, p)
        rows.append([lg])
    step = jax.jit(lambda qp, pool, t: serve_step(CFG, qp, pool, t))
    seqs = [list(p) for p in prompts]
    toks = np.array([[int(np.argmax(r[0]))] for r in rows], np.int32)
    for _ in range(8):
        for j in range(2):
            seqs[j].append(int(toks[j, 0]))
        lg, pool = step(qp, pool, jnp.asarray(toks))
        lg = np.asarray(lg)
        for j in range(2):
            rows[j].append(lg[j])
        toks = lg.argmax(-1)[:, None].astype(np.int32)
    served = np.concatenate([np.stack(r)[:, :ARCH["vocab"]] for r in rows])
    ref, ctl = (np.concatenate([
        _reference(seqs[j], len(p), 9, control=c)
        for j, p in enumerate(prompts)]) for c in (False, True))
    rms = lambda a: float(np.sqrt(np.mean(a * a)))
    assert rms(served - ref) <= RMS_TOL
    assert np.abs(served - ref).max() <= MAX_TOL
    assert rms(ctl - ref) > RMS_TOL or np.abs(ctl - ref).max() > MAX_TOL


def test_scheduler_tokens_match_alone_and_reference(qp):
    """Through ``Scheduler``: three requests sharing the pool (chunked
    prefill of 2-3 chunks, decode beside each other) emit the tokens each
    emits alone and through ``lockstep_generate``, and every served
    token's reference logit lies within MAX_TOL of the reference's best."""
    prompts = _prompts([40, 70, 12], seed=3)

    def serve(reqs, n_slots):
        eng = PooledEngine(CFG, qp, max_len=MAX_LEN, chunk_tokens=CHUNK)
        sched = Scheduler(CFG, qp, n_slots=n_slots, max_len=MAX_LEN,
                          engine=eng)
        assert sched.chunked
        for rid, p in reqs:
            sched.submit(Request(rid=rid, prompt=p, max_new_tokens=8))
        return {r.rid: r.tokens for r in sched.run_to_completion()}

    together = serve(list(enumerate(prompts)), 3)
    for rid, p in enumerate(prompts):
        assert together[rid] == serve([(rid, p)], 1)[rid]
        assert together[rid] == lockstep_generate(CFG, qp, p, 8,
                                                  max_len=MAX_LEN)
        toks = together[rid]
        lg = _reference(list(p) + toks[:-1], len(p), 8)
        gap = lg.max(-1) - lg[np.arange(8), toks]
        assert gap.max() <= MAX_TOL, (rid, gap)
