"""Property-based cases for core LOP / ternary / quantization invariants.

Split out of test_lop.py / test_ternary.py / test_quantization.py so those
modules' deterministic tests collect even when ``hypothesis`` is absent —
this whole module skips instead of killing collection.
"""
import pytest

hypothesis = pytest.importorskip("hypothesis")

import hypothesis.extra.numpy as hnp   # noqa: E402
import hypothesis.strategies as st     # noqa: E402
import jax                             # noqa: E402
import jax.numpy as jnp                # noqa: E402
import numpy as np                     # noqa: E402

from repro.core.lop import (features_to_pot, lop_features,  # noqa: E402
                            lop_scores, pack_features, pot, unpack_features)
from repro.core.quantization import (EPS, INT8_MAX,         # noqa: E402
                                     dequantize, quantize)
from repro.core.ternary import (pack_ternary, ternary_quantize,  # noqa: E402
                                unpack_ternary)

int8_vecs = hnp.arrays(np.int8, st.tuples(st.integers(2, 16).map(
    lambda d: 2 * d),), elements=st.integers(-127, 127))

ternary_mats = hnp.arrays(
    np.int8,
    st.tuples(st.integers(1, 16).map(lambda k: 4 * k), st.integers(1, 24)),
    elements=st.sampled_from([-1, 0, 1]))

finite_vecs = hnp.arrays(
    np.float32, hnp.array_shapes(min_dims=1, max_dims=3, min_side=1,
                                 max_side=32),
    elements=st.floats(-1e4, 1e4, width=32))


# ---------------------------------------------------------------------------
# LOP (paper §III-A)
# ---------------------------------------------------------------------------

@hypothesis.given(int8_vecs)
@hypothesis.settings(max_examples=50, deadline=None)
def test_surrogate_equals_pot_dot(x):
    """ŝ(q,k) = Σ sgn·sgn·2^(LO+LO) ≡ dot(pot(q), pot(k)) — the key
    TPU-mapping identity."""
    q = jnp.asarray(x)
    k = jnp.asarray(np.roll(x, 1))[None]
    s = int(lop_scores(q, k)[0])
    manual = sum(
        int(np.sign(a) * np.sign(b)) *
        2 ** (int(np.floor(np.log2(abs(a)))) + int(np.floor(np.log2(abs(b)))))
        for a, b in zip(np.asarray(q).tolist(), np.roll(x, 1).tolist())
        if a != 0 and b != 0)
    assert s == manual


@hypothesis.given(int8_vecs)
@hypothesis.settings(max_examples=50, deadline=None)
def test_feature_roundtrip(x):
    k = jnp.asarray(x)[None]
    f = lop_features(k)
    assert (np.asarray(features_to_pot(f)) == np.asarray(pot(k))).all()
    assert (np.asarray(unpack_features(pack_features(f))) ==
            np.asarray(f)).all()


# ---------------------------------------------------------------------------
# Ternary packing (BitNet b1.58)
# ---------------------------------------------------------------------------

@hypothesis.given(ternary_mats)
@hypothesis.settings(max_examples=50, deadline=None)
def test_pack_unpack_roundtrip(wt):
    packed = pack_ternary(jnp.asarray(wt))
    assert packed.shape == (wt.shape[0] // 4, wt.shape[1])
    back = np.asarray(unpack_ternary(packed, wt.shape[0]))
    assert (back == wt).all()


@hypothesis.given(hnp.arrays(np.float32, (8, 12),
                             elements=st.floats(-10, 10, width=32)))
@hypothesis.settings(max_examples=50, deadline=None)
def test_ternary_quantize_values(w):
    wt, gamma = ternary_quantize(jnp.asarray(w))
    vals = np.unique(np.asarray(wt))
    assert set(vals.tolist()) <= {-1, 0, 1}
    assert float(np.asarray(gamma).squeeze()) > 0   # γ is [1,1] (keepdims)


# ---------------------------------------------------------------------------
# Absmax quantization barrier
# ---------------------------------------------------------------------------

@hypothesis.given(finite_vecs)
@hypothesis.settings(max_examples=50, deadline=None)
def test_quantize_error_bound(x):
    """|dequant(quant(x)) − x| ≤ scale/2 (+eps) — the absmax contract."""
    qt = quantize(jnp.asarray(x))
    err = np.abs(np.asarray(dequantize(qt)) - x)
    bound = np.asarray(qt.scale) * 0.5 + 1e-6
    assert (err <= np.broadcast_to(bound, err.shape) + 1e-6).all()


@hypothesis.given(finite_vecs)
@hypothesis.settings(max_examples=25, deadline=None)
def test_quantize_int8_range(x):
    qt = quantize(jnp.asarray(x))
    v = np.asarray(qt.values)
    assert v.dtype == np.int8
    assert v.min() >= -127 and v.max() <= 127


# ---------------------------------------------------------------------------
# Autotuned tiling variants (DESIGN.md §Autotuning)
# ---------------------------------------------------------------------------

@hypothesis.given(st.data())
@hypothesis.settings(max_examples=50, deadline=None)
def test_ktiled_absmax_equals_single_pass_bitwise(data):
    """The two-pass k-tiled barrier (kernels/qlinear.py, bkq > 0): fold
    per-tile absmax maxima, freeze the scale, then quantize tile-by-tile
    — BITWISE the single-pass absmax quantize for EVERY (k, bk) split,
    because f32 max is exact and round/clip are elementwise against the
    frozen scale."""
    k = data.draw(st.integers(1, 24).map(lambda d: 4 * d), label="k")
    bk = data.draw(st.sampled_from(
        [d for d in range(1, k + 1) if k % d == 0]), label="bk")
    m = data.draw(st.integers(1, 6), label="m")
    x = data.draw(hnp.arrays(np.float32, (m, k),
                             elements=st.floats(-1e4, 1e4, width=32)))
    want = quantize(jnp.asarray(x))
    am = jnp.zeros((m, 1), jnp.float32)
    for j in range(k // bk):
        tile = jnp.asarray(x[:, j * bk:(j + 1) * bk])
        am = jnp.maximum(am, jnp.max(jnp.abs(tile), axis=-1, keepdims=True))
    scale = jnp.maximum(am, EPS).astype(jnp.float32) / INT8_MAX
    tiles = [jnp.clip(jnp.round(jnp.asarray(x[:, j * bk:(j + 1) * bk])
                                .astype(jnp.float32) / scale),
                      -INT8_MAX, INT8_MAX).astype(jnp.int8)
             for j in range(k // bk)]
    assert (np.asarray(scale) == np.asarray(want.scale)).all()
    assert (np.asarray(jnp.concatenate(tiles, -1)) ==
            np.asarray(want.values)).all()


@hypothesis.given(st.data())
@hypothesis.settings(max_examples=10, deadline=None)
def test_prefill_query_row_tiling_bitwise(data):
    """The prefill kernel's third grid axis (bq query-row tiles): every
    legal bq is BITWISE the untiled launch — the kv gate is loose enough
    to be row-independent, so masked folds are exact no-ops."""
    from repro.kernels.prefill_attention import fused_prefill_attention
    r, d, m, block, chunk = 32, 8, 32, 16, 8
    seed = data.draw(st.integers(0, 2 ** 16), label="seed")
    bq = data.draw(st.sampled_from([8, 16, 32]), label="bq")
    kv_len_v = data.draw(st.integers(0, m), label="kv_len")
    r_ = np.random.default_rng(seed)
    qi = jnp.asarray(r_.integers(-127, 128, (1, r, d)), jnp.int8)
    qsc = jnp.asarray(r_.uniform(0.005, 0.02, (1, r, 1)), jnp.float32)
    kc = jnp.asarray(r_.integers(-127, 128, (1, m, d)), jnp.int8)
    vc = jnp.asarray(r_.integers(-127, 128, (1, m, d)), jnp.int8)
    ks = jnp.asarray(r_.uniform(0.005, 0.02, (1, m, 1)), jnp.float32)
    vs = jnp.asarray(r_.uniform(0.005, 0.02, (1, m, 1)), jnp.float32)
    kv_len = jnp.asarray([kv_len_v], jnp.int32)
    po = jnp.zeros((1,), jnp.int32)
    kw = dict(hkv=1, chunk=chunk, block=block, causal=True, window=0,
              softmax_scale=d ** -0.5, interpret=True)
    whole = fused_prefill_attention(qi, qsc, kc, vc, ks, vs, kv_len, po,
                                    bq=0, **kw)
    tiled = fused_prefill_attention(qi, qsc, kc, vc, ks, vs, kv_len, po,
                                    bq=bq, **kw)
    assert (np.asarray(tiled) == np.asarray(whole)).all()
