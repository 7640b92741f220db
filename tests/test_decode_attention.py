"""Fused batched decode kernel (interpret mode) vs the jnp oracle.

Covers the ``ops.decode_attention`` contract across GQA shapes (MHA and
4-way grouping), shared-select on/off, dense vs LOP, SWA windows, slot
pools with retired lanes (``new_len == 0`` lanes must emit exactly zero),
the SP shard contract (``pos_offset`` + unnormalized stats merge), and the
engine-level flag→config migration (``gqa_shared_select``/``int8_logits``
as ModelConfig fields).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lop import lop_features, pack_features
from repro.kernels import autotune, ops

rng = np.random.default_rng(7)


def _setup(b, h, hkv, m, dh):
    qi = jnp.asarray(rng.integers(-60, 61, (b, h, dh)), jnp.int8)
    qs = jnp.asarray(rng.uniform(0.005, 0.02, (b, h, 1)), jnp.float32)
    k = jnp.asarray(rng.integers(-60, 61, (b, hkv, m, dh)), jnp.int8)
    v = jnp.asarray(rng.integers(-60, 61, (b, hkv, m, dh)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.005, 0.02, (b, hkv, m)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.005, 0.02, (b, hkv, m)), jnp.float32)
    feat = pack_features(lop_features(k))
    return qi, qs, k, v, ks, vs, feat


@pytest.mark.parametrize("h,hkv", [(4, 4), (8, 2)])
@pytest.mark.parametrize("shared", [False, True])
@pytest.mark.parametrize("window", [0, 48])
@pytest.mark.parametrize("dh", [32, 100])      # 100: d/2 = 50, off the lanes
def test_fused_lop_matches_ref(h, hkv, shared, window, dh):
    b, m, block, k_keep = 2, 256, 32, 3
    args = _setup(b, h, hkv, m, dh)
    new_len = jnp.asarray([197, 64], jnp.int32)
    kw = dict(block=block, k_keep=k_keep, window=window, use_lop=True,
              shared_select=shared)
    o_k = ops.decode_attention(*args, new_len, impl="pallas", **kw)
    o_r = ops.decode_attention(*args, new_len, impl="ref", **kw)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=1e-4)


@pytest.mark.parametrize("h,hkv", [(4, 4), (8, 2)])
@pytest.mark.parametrize("window", [0, 48])
def test_fused_dense_matches_ref(h, hkv, window):
    b, m, dh, block = 2, 256, 32, 32
    args = _setup(b, h, hkv, m, dh)
    new_len = jnp.asarray([211, 32], jnp.int32)
    kw = dict(block=block, k_keep=4, window=window, use_lop=False)
    o_k = ops.decode_attention(*args, new_len, impl="pallas", **kw)
    o_r = ops.decode_attention(*args, new_len, impl="ref", **kw)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=1e-4)


def test_lop_at_full_keep_equals_dense():
    """K = NB candidates → the sparse pipeline is exact (paper's K=M)."""
    b, h, hkv, m, dh, block = 2, 8, 2, 256, 32, 32
    args = _setup(b, h, hkv, m, dh)
    new_len = jnp.asarray([222, 100], jnp.int32)
    o_lop = ops.decode_attention(*args, new_len, block=block,
                                 k_keep=m // block, use_lop=True,
                                 impl="pallas")
    o_dense = ops.decode_attention(*args, new_len, block=block, k_keep=1,
                                   use_lop=False, impl="pallas")
    np.testing.assert_allclose(np.asarray(o_lop), np.asarray(o_dense),
                               atol=1e-4)


@pytest.mark.parametrize("use_lop", [True, False])
@pytest.mark.parametrize("shared", [False, True])
def test_retired_lanes_emit_exact_zero(use_lop, shared):
    """Slot-pool contract: a lane with new_len == 0 (retired / never
    occupied) produces bitwise-zero attention output on BOTH impls, no
    matter what stale bytes its cache rows hold."""
    b, h, hkv, m, dh, block = 3, 8, 2, 128, 32, 32
    args = _setup(b, h, hkv, m, dh)
    new_len = jnp.asarray([90, 0, 0], jnp.int32)     # lanes 1, 2 retired
    kw = dict(block=block, k_keep=2, use_lop=use_lop, shared_select=shared)
    for impl in ("pallas", "ref"):
        out = ops.decode_attention(*args, new_len, impl=impl, **kw)
        assert np.isfinite(np.asarray(out)).all(), impl
        assert (np.asarray(out[1:]) == 0.0).all(), impl
        assert np.abs(np.asarray(out[0])).max() > 0.0, impl


@pytest.mark.parametrize("use_lop", [True, False])
def test_shard_stats_merge_matches_global(use_lop):
    """The SP contract: per-shard calls with pos_offset + return_stats
    merge flash-decoding style into the unsharded result. Dense is exact;
    LOP at full keep (quota K/2 per half) is exact too since every valid
    block still gets selected."""
    b, h, hkv, m, dh, block = 2, 8, 2, 256, 32, 32
    args = _setup(b, h, hkv, m, dh)
    qi, qs, k, v, ks, vs, feat = args
    new_len = jnp.asarray([230, 120], jnp.int32)
    nb = m // block
    o_g = ops.decode_attention(*args, new_len, block=block, k_keep=nb,
                               use_lop=use_lop, impl="pallas")
    half = m // 2
    parts = []
    for sh in range(2):
        sl = slice(sh * half, (sh + 1) * half)
        parts.append(ops.decode_attention(
            qi, qs, k[:, :, sl], v[:, :, sl], ks[:, :, sl], vs[:, :, sl],
            feat[:, :, sl], new_len, block=block, k_keep=nb // 2,
            use_lop=use_lop, pos_offset=sh * half, return_stats=True,
            impl="pallas"))
    (o0, m0, l0), (o1, m1, l1) = parts
    m_g = jnp.maximum(m0, m1)
    w0, w1 = jnp.exp(m0 - m_g), jnp.exp(m1 - m_g)
    l_g = l0 * w0 + l1 * w1
    acc = o0 * (l0 * w0) + o1 * (l1 * w1)
    merged = acc / jnp.maximum(l_g, 1e-20)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(o_g),
                               atol=1e-4)


def test_stats_agree_between_impls():
    b, h, hkv, m, dh, block = 2, 4, 4, 128, 32, 32
    args = _setup(b, h, hkv, m, dh)
    new_len = jnp.asarray([100, 0], jnp.int32)
    kw = dict(block=block, k_keep=2, use_lop=True, return_stats=True)
    o_k, m_k, l_k = ops.decode_attention(*args, new_len, impl="pallas", **kw)
    o_r, m_r, l_r = ops.decode_attention(*args, new_len, impl="ref", **kw)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=1e-4)
    np.testing.assert_allclose(np.asarray(m_k), np.asarray(m_r), atol=1e-4)
    np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_r), rtol=1e-4)
    # retired lane: no live candidates → ℓ = 0 on both impls
    assert (np.asarray(l_k[1]) == 0.0).all()
    assert (np.asarray(l_r[1]) == 0.0).all()


# ---------------------------------------------------------------------------
# Autotune tiling matrix (DESIGN.md §Autotuning)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_slots", [1, 2])
@pytest.mark.parametrize("shared", [False, True])
def test_decode_n_slots_matrix_bitwise(n_slots, shared):
    """Swept candidate buffer counts are pure pipelining: BITWISE the
    default n_slots=2 launch and allclose the ref oracle — at the
    capacity-boundary K-slot (k_keep == n_blocks, every block kept)."""
    b, h, hkv, m, dh, block = 2, 8, 2, 128, 32, 32
    assert autotune.valid_params(
        "decode", {"bhg": b * hkv, "g": h // hkv, "d": dh, "m": m,
                   "block": block, "k_keep": m // block},
        {"n_slots": n_slots})
    args = _setup(b, h, hkv, m, dh)
    new_len = jnp.asarray([97, 64], jnp.int32)
    kw = dict(block=block, k_keep=m // block, use_lop=True,
              shared_select=shared)
    o_ref = ops.decode_attention(*args, new_len, impl="ref", **kw)
    o_def = ops.decode_attention(*args, new_len, impl="pallas", **kw)
    with autotune.override("decode", n_slots=n_slots):
        o_t = ops.decode_attention(*args, new_len, impl="pallas", **kw)
    np.testing.assert_array_equal(np.asarray(o_t), np.asarray(o_def))
    np.testing.assert_allclose(np.asarray(o_t), np.asarray(o_ref),
                               atol=1e-4)


@pytest.mark.parametrize("use_lop", [True, False], ids=["lop", "dense"])
def test_lop_phases_carry_their_scopes(use_lop):
    """The screen phase (screen launch + candidate selection) and the
    exact launch lower under the scopes ``lop_screen`` / ``lop_exact``,
    which a profiler trace shows in each device op's name; the dense
    launch has neither."""
    from repro.kernels.decode_attention import fused_decode_attention
    bh, g, d, m = 4, 2, 32, 128
    sds = jax.ShapeDtypeStruct
    args = (sds((bh, g, d), jnp.int8), sds((bh, g, 1), jnp.float32),
            sds((bh, m, d), jnp.int8), sds((bh, m, d), jnp.int8),
            sds((bh, m, 1), jnp.float32), sds((bh, m, 1), jnp.float32),
            sds((bh, m, d // 2), jnp.uint8), sds((bh // 2,), jnp.int32),
            sds((1,), jnp.int32))
    text = fused_decode_attention.lower(
        *args, hkv=2, block=32, k_keep=2, window=0, softmax_scale=0.1,
        use_lop=use_lop, interpret=True).as_text(debug_info=True)
    for scope in ("lop_screen/", "lop_exact/"):
        assert (scope in text) == use_lop, scope


@pytest.mark.parametrize("n_slots", [3, 4])
def test_decode_n_slots_beyond_double_buffering_rejected(n_slots):
    """Mosaic pipelines at most two buffers per input: deeper candidate
    pipelines fail the screen, and an override of one falls back to the
    default."""
    dims = {"bhg": 4, "g": 4, "d": 32, "m": 128, "block": 32, "k_keep": 4}
    assert not autotune.valid_params("decode", dims, {"n_slots": n_slots})
    with autotune.override("decode", n_slots=n_slots):
        assert autotune.lookup("decode", dims) == {}


def test_decode_single_row_lane_n_slots():
    """bm = 1 decode: a single-lane, single-group (B=1, MHA h=1) launch
    stays exact across every slot count."""
    b, h, hkv, m, dh, block = 1, 1, 1, 64, 32, 32
    args = _setup(b, h, hkv, m, dh)
    new_len = jnp.asarray([39], jnp.int32)
    kw = dict(block=block, k_keep=2, use_lop=True)
    o_ref = ops.decode_attention(*args, new_len, impl="ref", **kw)
    for ns in (1, 2):
        with autotune.override("decode", n_slots=ns):
            o_t = ops.decode_attention(*args, new_len, impl="pallas", **kw)
        np.testing.assert_allclose(np.asarray(o_t), np.asarray(o_ref),
                                   atol=1e-4, err_msg=f"n_slots={ns}")


# ---------------------------------------------------------------------------
# Engine-level: flag→config migration
# ---------------------------------------------------------------------------

def _engine_cell(cfg):
    from repro.models.transformer import init_params
    from repro.serving.engine import prefill, serve_step
    from repro.serving.quantize import quantize_params
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    qp = quantize_params(cfg, params)
    r = np.random.default_rng(9)
    tokens = jnp.asarray(r.integers(0, cfg.vocab, (2, 21)), jnp.int32)
    logits_full, _ = prefill(cfg, qp, tokens, max_len=24)
    _, cache = prefill(cfg, qp, tokens[:, :20], max_len=24)
    logits_dec, _ = serve_step(cfg, qp, cache, tokens[:, 20:21])
    return logits_full, logits_dec


def test_config_fields_replace_env_flags():
    """gqa_shared_select / int8_logits as ModelConfig fields steer the
    decode path without any env var: shared selection at keep=1.0 stays
    exact, and integer-domain prefill logits match the f32 path."""
    from tests.test_models_smoke import _reduced
    cfg = _reduced("mistral-nemo-12b").replace(lop_keep=1.0)
    base_full, base_dec = _engine_cell(
        cfg.replace(gqa_shared_select=False, int8_logits=False))
    flag_full, flag_dec = _engine_cell(
        cfg.replace(gqa_shared_select=True, int8_logits=True))
    rel_dec = float(jnp.max(jnp.abs(flag_dec - base_dec))
                    / (jnp.max(jnp.abs(base_dec)) + 1e-9))
    rel_full = float(jnp.linalg.norm(flag_full - base_full)
                     / (jnp.linalg.norm(base_full) + 1e-9))
    assert rel_dec < 1e-5, rel_dec
    assert rel_full < 1e-4, rel_full


def test_resolve_decode_flags_pins_fields(monkeypatch):
    from repro.configs.base import resolve_decode_flags
    from tests.test_models_smoke import _reduced
    cfg = _reduced("stablelm-1.6b")
    assert cfg.gqa_shared_select is None and cfg.int8_logits is None
    monkeypatch.setenv("REPRO_GQA_SHARED_SELECT", "1")
    monkeypatch.delenv("REPRO_INT8_LOGITS", raising=False)
    r = resolve_decode_flags(cfg)
    assert r.gqa_shared_select is True and r.int8_logits is False
    # explicit fields win over the env
    r2 = resolve_decode_flags(cfg.replace(gqa_shared_select=False,
                                          int8_logits=True))
    assert r2.gqa_shared_select is False and r2.int8_logits is True
    # already-pinned configs pass through untouched
    assert resolve_decode_flags(r2) is r2
