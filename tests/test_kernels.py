"""Pallas kernels (interpret mode) vs pure-jnp oracles — shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lop import (LO_ZERO, features_to_pot, lop_features,
                            pack_features, unpack_features)
from repro.core.ternary import make_ternary_weight
from repro.kernels import ops, ref
from repro.kernels.lop_scores import nibble_planes_to_pot

rng = np.random.default_rng(42)


@pytest.mark.parametrize("m,k,n", [
    (8, 128, 128), (48, 512, 256), (130, 1024, 128), (1, 256, 512),
])
def test_ternary_matmul_exact(m, k, n):
    x = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    w = jnp.asarray(rng.standard_normal((k, n)), np.float32) * 0.02
    tw = make_ternary_weight(w)
    y_k = ops.ternary_matmul(x, tw, impl="pallas")
    y_r = ops.ternary_matmul(x, tw, impl="ref")
    assert y_k.dtype == jnp.int32
    assert (np.asarray(y_k) == np.asarray(y_r)).all()


def test_ternary_matmul_leading_dims():
    x = jnp.asarray(rng.integers(-50, 51, (2, 3, 256)), jnp.int8)
    w = jnp.asarray(rng.standard_normal((256, 128)), np.float32) * 0.02
    tw = make_ternary_weight(w)
    y = ops.ternary_matmul(x, tw, impl="pallas")
    assert y.shape == (2, 3, 128)
    assert (np.asarray(y) ==
            np.asarray(ops.ternary_matmul(x, tw, impl="ref"))).all()


@pytest.mark.parametrize("shape", [(1, 128), (3, 5, 256), (300, 3200)])
def test_rmsnorm_kernel_matches_oracle(shape):
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32) * 3.0
    g = jnp.asarray(rng.uniform(0.5, 1.5, (shape[-1],)), jnp.float32)
    y_k = ops.rmsnorm(x, g, impl="pallas")
    y_r = ops.rmsnorm(x, g, impl="ref")
    assert y_k.shape == x.shape and y_k.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=1e-6, atol=1e-6)


def test_rmsnorm_kernel_rows_independent_of_call_size():
    """A row's result depends on that row alone: the rows of a 300-row
    call (a whole-prompt prefill), of a 128-row call (a prefill chunk)
    and of a 1-row call agree bit for bit."""
    x = jnp.asarray(rng.standard_normal((300, 256)), jnp.float32)
    g = jnp.asarray(rng.uniform(0.5, 1.5, (256,)), jnp.float32)
    norm = jax.jit(lambda x: ops.rmsnorm(x, g, impl="pallas"))
    whole = np.asarray(norm(x))
    assert (np.asarray(norm(x[:128])) == whole[:128]).all()
    for i in (0, 7, 8, 299):
        assert (np.asarray(norm(x[i:i + 1]))[0] == whole[i]).all(), i


def test_nibble_planes_decode_every_byte():
    """Every packed byte decodes, plane by plane, to the pot values of its
    two features: the low nibble is feature 2i, the high nibble 2i + 1.
    The 256 bytes hold every (sgn‖LO) pair: zero (LO_ZERO) under both
    signs, and ±2^LO for every LO."""
    packed = jnp.arange(256, dtype=jnp.int32).astype(jnp.uint8)[:, None]
    even, odd = nibble_planes_to_pot(packed)             # [256, 1] each
    assert even.dtype == odd.dtype == jnp.int8
    want = np.asarray(features_to_pot(unpack_features(packed)))  # [256, 2]
    assert (np.asarray(even)[:, 0] == want[:, 0]).all()
    assert (np.asarray(odd)[:, 0] == want[:, 1]).all()

    def nibble_pot(n):
        lo, sgn = n & 0x7, n >> 3
        return 0 if lo == LO_ZERO else (-1) ** sgn * 2 ** min(lo, 6)
    assert [int(v) for v in np.asarray(even)[:, 0]] == [
        nibble_pot(b & 0xF) for b in range(256)]
    assert [int(v) for v in np.asarray(odd)[:, 0]] == [
        nibble_pot(b >> 4) for b in range(256)]


@pytest.mark.parametrize("g,m,d", [(12, 1024, 128), (1, 512, 64),
                                   (40, 2048, 128), (12, 1024, 100),
                                   (1, 512, 100)])
def test_lop_scores_kernel(g, m, d):
    q = jnp.asarray(rng.integers(-127, 128, (g, d)), jnp.int8)
    kc = jnp.asarray(rng.integers(-127, 128, (m, d)), jnp.int8)
    feat = pack_features(lop_features(kc))
    s_k = ops.lop_screen(q, feat, impl="pallas")
    s_r = ops.lop_screen(q, feat, impl="ref")
    assert (np.asarray(s_k) == np.asarray(s_r)).all()


@pytest.mark.parametrize("s,d,causal,window", [
    (256, 64, True, 0), (512, 128, True, 0), (512, 128, False, 0),
    (512, 64, True, 128),
])
def test_flash_prefill_kernel(s, d, causal, window):
    q = jnp.asarray(rng.integers(-60, 61, (s, d)), jnp.int8)
    k = jnp.asarray(rng.integers(-60, 61, (s, d)), jnp.int8)
    v = jnp.asarray(rng.integers(-60, 61, (s, d)), jnp.int8)
    sc = [jnp.asarray(rng.uniform(0.005, 0.02, (s, 1)), jnp.float32)
          for _ in range(3)]
    sm = 1.0 / np.sqrt(d)
    o_k = ops.flash_prefill(q, k, v, *sc, softmax_scale=sm, causal=causal,
                            window=window, impl="pallas")
    o_r = ops.flash_prefill(q, k, v, *sc, softmax_scale=sm, causal=causal,
                            window=window, impl="ref")
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=1e-4)


@pytest.mark.parametrize("g,nb,block", [(6, 4, 128), (1, 2, 64), (8, 8, 32)])
def test_sparse_decode_kernel(g, nb, block):
    m, d = 16 * block, 64
    kc = jnp.asarray(rng.integers(-60, 61, (m, d)), jnp.int8)
    vc = jnp.asarray(rng.integers(-60, 61, (m, d)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.005, 0.02, (m, 1)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.005, 0.02, (m, 1)), jnp.float32)
    q = jnp.asarray(rng.integers(-60, 61, (g, d)), jnp.int8)
    qs = jnp.asarray(rng.uniform(0.005, 0.02, (g, 1)), jnp.float32)
    bidx = jnp.asarray(rng.choice(16, nb, replace=False), jnp.int32)
    gate = np.ones(nb, np.int32)
    gate[-1] = 0                                     # one gated-off block
    end = rng.integers(1, block + 1, nb).astype(np.int32)
    start = np.minimum(rng.integers(0, block, nb), end - 1).astype(np.int32)
    gt = jnp.asarray(np.concatenate([gate, end, start]), jnp.int32)
    sm = 1.0 / np.sqrt(d)
    o_k = ops.sparse_decode(q, kc, vc, qs, ks, vs, bidx, gt, block=block,
                            softmax_scale=sm, impl="pallas")
    o_r = ops.sparse_decode(q, kc, vc, qs, ks, vs, bidx, gt, block=block,
                            softmax_scale=sm, impl="ref")
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=1e-4)


def test_sparse_decode_equals_dense_when_all_blocks():
    """Sparse kernel over ALL blocks == dense attention (exactness)."""
    m, d, block = 512, 64, 64
    nb = m // block
    kc = jnp.asarray(rng.integers(-60, 61, (m, d)), jnp.int8)
    vc = jnp.asarray(rng.integers(-60, 61, (m, d)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.005, 0.02, (m, 1)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.005, 0.02, (m, 1)), jnp.float32)
    q = jnp.asarray(rng.integers(-60, 61, (4, d)), jnp.int8)
    qs = jnp.asarray(rng.uniform(0.005, 0.02, (4, 1)), jnp.float32)
    bidx = jnp.arange(nb, dtype=jnp.int32)
    gt = jnp.asarray(np.concatenate([np.ones(nb), np.full(nb, block),
                                     np.zeros(nb)]).astype(np.int32))
    sm = 1.0 / np.sqrt(d)
    o = ops.sparse_decode(q, kc, vc, qs, ks, vs, bidx, gt, block=block,
                          softmax_scale=sm, impl="pallas")
    logits = (q.astype(np.int32) @ np.asarray(kc, np.int32).T
              ).astype(np.float32)
    logits = logits * np.asarray(qs) * np.asarray(ks).T * sm
    p = jax.nn.softmax(jnp.asarray(logits), -1)
    o_dense = np.asarray(p) @ (np.asarray(vc, np.float32) * np.asarray(vs))
    np.testing.assert_allclose(np.asarray(o), o_dense, atol=1e-4)
