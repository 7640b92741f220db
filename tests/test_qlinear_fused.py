"""Fused TINT projection path (DESIGN.md §TINT-projection-fusion).

Every case pins the tentpole contract: the one-dispatch fused entries
(`ops.qlinear_fused` / `ops.ffn_fused`, barrier + packed-ternary GEMM +
epilogue in one kernel) are **bitwise** the unfused chain they replaced
(jnp absmax quantize → `ops.ternary_matmul` → jnp dequant/bias/act),
under BOTH dispatch arms, across the shapes the engine actually runs:
decode GEMV rows (b = 1..4), prefill chunk rows, fused-QKV segment
splits, whole-FFN gated/ungated, and grouped expert stacks.

All comparisons run jitted end to end: XLA compiles the absmax division
differently inside a fused computation than as a standalone eager op
(1-ulp scale difference), so bitwise equality is defined — as in the
engine — under jit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantization import quantize
from repro.core.ternary import TernaryWeight, make_ternary_weight
from repro.kernels import autotune, ops
from repro.kernels.qlinear import apply_act

rng = np.random.default_rng(7)

ARMS = ("ref", "pallas")


def _node(k, n, scale=0.02):
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32) * scale
    return make_ternary_weight(w)


def _unfused(tw, x, bias=None, act=None):
    """The pre-fusion chain, written out (the equivalence oracle)."""
    xq = quantize(x)
    acc = ops.ternary_matmul(xq.values, tw, impl="ref")
    y = acc.astype(jnp.float32) * xq.scale * jnp.asarray(
        tw.scale, jnp.float32).reshape(())
    if bias is not None:
        y = y + bias
    return apply_act(y, act)


@pytest.mark.parametrize("m", [1, 2, 3, 4, 48, 130])
@pytest.mark.parametrize("impl", ARMS)
def test_qlinear_fused_bitwise_vs_unfused(m, impl):
    """Decode GEMV rows (m = B ≤ 4) and prefill-chunk rows (m = B·C)."""
    k, n = 128, 96
    tw = _node(k, n)
    b = jnp.asarray(rng.standard_normal((n,)), jnp.float32) * 0.1
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    want = jax.jit(lambda x: _unfused(tw, x, bias=b))(x)
    got = jax.jit(lambda x: ops.qlinear_fused(
        x, tw.packed, jnp.asarray(tw.scale).reshape(1, 1), b,
        impl=impl))(x)
    assert (np.asarray(got) == np.asarray(want)).all()


@pytest.mark.parametrize("impl", ARMS)
def test_qlinear_fused_leading_dims(impl):
    """Engine shapes are [B, S, k] — lead dims flatten inside the op."""
    k, n = 64, 128
    tw = _node(k, n)
    x = jnp.asarray(rng.standard_normal((2, 5, k)), jnp.float32)
    want = jax.jit(lambda x: _unfused(tw, x))(x)
    got = jax.jit(lambda x: ops.qlinear_fused(
        x, tw.packed, jnp.asarray(tw.scale).reshape(1, 1), impl=impl))(x)
    assert got.shape == (2, 5, n)
    assert (np.asarray(got) == np.asarray(want)).all()


@pytest.mark.parametrize("impl", ARMS)
@pytest.mark.parametrize("m", [1, 4, 16])
def test_fused_qkv_segments_bitwise(m, impl):
    """One fused QKV dispatch == three per-projection dispatches, per
    segment, bitwise — the per-column γ row carries each segment's scalar.
    Its 160 columns are off the 128-lane grid: one whole-dim block."""
    k, nq, nkv = 128, 96, 32
    tws = [_node(k, n) for n in (nq, nkv, nkv)]
    packed = jnp.concatenate([t.packed for t in tws], -1)
    scale = jnp.concatenate(
        [jnp.broadcast_to(jnp.asarray(t.scale, jnp.float32).reshape(1, 1),
                          (1, t.shape[1])) for t in tws], -1)
    bias = jnp.asarray(rng.standard_normal((nq + 2 * nkv,)),
                       jnp.float32) * 0.1
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)

    got = jax.jit(lambda x: ops.qlinear_fused(x, packed, scale, bias,
                                              impl=impl))(x)
    off = 0
    for tw, n in zip(tws, (nq, nkv, nkv)):
        want = jax.jit(
            lambda x, tw=tw, o=off, n=n: _unfused(tw, x,
                                                  bias=bias[o:o + n]))(x)
        assert (np.asarray(got[..., off:off + n]) ==
                np.asarray(want)).all(), (off, n)
        off += n


@pytest.mark.parametrize("impl", ARMS)
@pytest.mark.parametrize("gated,act", [(True, "silu"), (False, "gelu"),
                                       (True, "squared_relu")])
def test_ffn_fused_bitwise_vs_unfused(gated, act, impl):
    """Whole-FFN fusion: act(x·Wg)·(x·Wu) → hidden absmax barrier → ·Wd
    in one dispatch == the three-dispatch unfused chain, bitwise."""
    d, f, m = 128, 256, 5
    twu, twd = _node(d, f, 0.05), _node(f, d, 0.05)
    twg = _node(d, f, 0.05) if gated else None

    def unfused(x):
        if gated:
            h = apply_act(_unfused(twg, x), act) * _unfused(twu, x)
        else:
            h = apply_act(_unfused(twu, x), act)
        return _unfused(twd, h)

    if gated:
        gu_packed = jnp.concatenate([twg.packed, twu.packed], -1)
        gu_scale = jnp.concatenate(
            [jnp.broadcast_to(jnp.asarray(t.scale).reshape(1, 1), (1, f))
             for t in (twg, twu)], -1)
    else:
        gu_packed = twu.packed
        gu_scale = jnp.asarray(twu.scale).reshape(1, 1)

    x = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
    want = jax.jit(unfused)(x)
    got = jax.jit(lambda x: ops.ffn_fused(
        x, gu_packed, gu_scale, twd.packed,
        jnp.asarray(twd.scale).reshape(1, 1), gated=gated, act=act,
        impl=impl))(x)
    assert (np.asarray(got) == np.asarray(want)).all()


def _expert_stack(e, k, n, scale=0.05):
    packs, scales = [], []
    for _ in range(e):
        tw = _node(k, n, scale)
        packs.append(tw.packed)
        scales.append(jnp.asarray(tw.scale).reshape(1, 1))
    return jnp.stack(packs), jnp.stack(scales)


@pytest.mark.parametrize("impl", ARMS)
def test_grouped_expert_qlinear_bitwise(impl):
    """Expert-as-grid-axis grouped GEMM == the per-expert vmap chain."""
    e, c, k, n = 4, 6, 64, 96
    packed, scale = _expert_stack(e, k, n)
    x = jnp.asarray(rng.standard_normal((e, c, k)), jnp.float32)

    def per_expert(x):
        def one(xe, pe, se):
            tw = TernaryWeight(packed=pe, scale=1.0, shape=(k, n))
            xq = quantize(xe)
            acc = ops.ternary_matmul(xq.values, tw, impl="ref")
            return acc.astype(jnp.float32) * xq.scale * se.reshape(())
        return jax.vmap(one)(x, packed, scale)

    want = jax.jit(per_expert)(x)
    got = jax.jit(lambda x: ops.qlinear_fused(x, packed, scale,
                                              impl=impl))(x)
    assert (np.asarray(got) == np.asarray(want)).all()


@pytest.mark.parametrize("impl", ARMS)
def test_grouped_expert_ffn_bitwise(impl):
    """A whole MoE layer's expert FFNs as ONE dispatch == the per-expert
    per-projection chain, bitwise."""
    e, c, d, f = 3, 5, 64, 128
    gp, gs = _expert_stack(e, d, f)
    up, us = _expert_stack(e, d, f)
    dp_, ds = _expert_stack(e, f, d)
    gu_packed = jnp.concatenate([gp, up], -1)
    gu_scale = jnp.concatenate([jnp.broadcast_to(gs, (e, 1, f)),
                                jnp.broadcast_to(us, (e, 1, f))], -1)
    x = jnp.asarray(rng.standard_normal((e, c, d)), jnp.float32)

    def per_expert(x):
        def one(xe, a, b_, c_, ga, gb, gc):
            def lin(p, g, h):
                tw = TernaryWeight(packed=p, scale=1.0,
                                   shape=(p.shape[0] * 4, p.shape[1]))
                hq = quantize(h)
                acc = ops.ternary_matmul(hq.values, tw, impl="ref")
                return acc.astype(jnp.float32) * hq.scale * g.reshape(())
            h = jax.nn.silu(lin(a, ga, xe)) * lin(b_, gb, xe)
            return lin(c_, gc, h)
        return jax.vmap(one)(x, gp, up, dp_, gs, us, ds)

    want = jax.jit(per_expert)(x)
    got = jax.jit(lambda x: ops.ffn_fused(x, gu_packed, gu_scale, dp_, ds,
                                          gated=True, act="silu",
                                          impl=impl))(x)
    assert (np.asarray(got) == np.asarray(want)).all()


def _gated_unfused(twg, twu, twd, x):
    h = apply_act(_unfused(twg, x), "silu") * _unfused(twu, x)
    return _unfused(twd, h)


def _gu_node(twg, twu):
    f = twu.shape[1]
    return (jnp.concatenate([twg.packed, twu.packed], -1),
            jnp.concatenate(
                [jnp.broadcast_to(jnp.asarray(t.scale).reshape(1, 1), (1, f))
                 for t in (twg, twu)], -1))


@pytest.mark.parametrize("impl", ARMS)
def test_ffn_fused_multi_row_tile_bitwise(impl):
    """More rows than one 128-row tile (a whole-prompt prefill): every
    row tile lands in its own output rows, bitwise the unfused chain."""
    d, f, m = 128, 256, 200
    twg, twu, twd = _node(d, f, 0.05), _node(d, f, 0.05), _node(f, d, 0.05)
    gu_packed, gu_scale = _gu_node(twg, twu)
    x = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
    want = jax.jit(lambda x: _gated_unfused(twg, twu, twd, x))(x)
    got = jax.jit(lambda x: ops.ffn_fused(
        x, gu_packed, gu_scale, twd.packed,
        jnp.asarray(twd.scale).reshape(1, 1), gated=True, act="silu",
        impl=impl))(x)
    assert np.isfinite(np.asarray(got)).all()
    assert (np.asarray(got) == np.asarray(want)).all()


@pytest.mark.parametrize("impl", ARMS)
def test_ffn_hidden_padding_bitwise(impl):
    """A hidden width off the 128-lane grid (f = 200) is padded with zero
    codes when quantize_params fuses the FFN. Zero columns leave the
    hidden vector and its absmax unchanged, so the padded node serves
    bitwise the unpadded unfused chain."""
    from types import SimpleNamespace

    from repro.serving.quantize import quantize_params

    d, f, m = 128, 200, 5
    ws = {nm: jnp.asarray(rng.standard_normal(shape), jnp.float32) * 0.05
          for nm, shape in (("w_gate", (d, f)), ("w_up", (d, f)),
                            ("w_down", (f, d)))}
    node = quantize_params(SimpleNamespace(quant="ternary"),
                           {"ffn": {nm: {"w": w} for nm, w in ws.items()}}
                           )["ffn"]
    assert node["gu_packed"].shape == (d // 4, 2 * 256)
    assert node["down_packed"].shape == (256 // 4, d)
    twg, twu, twd = (make_ternary_weight(ws[nm])
                     for nm in ("w_gate", "w_up", "w_down"))
    gu = np.asarray(node["gu_packed"])
    assert (gu[:, :f] == np.asarray(twg.packed)).all()
    assert (gu[:, 256:256 + f] == np.asarray(twu.packed)).all()
    assert not gu[:, f:256].any() and not gu[:, 256 + f:].any()

    x = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
    want = jax.jit(lambda x: _gated_unfused(twg, twu, twd, x))(x)
    got = jax.jit(lambda x: ops.ffn_fused(
        x, node["gu_packed"], node["gu_scale"], node["down_packed"],
        node["down_scale"], gated=True, act="silu", impl=impl))(x)
    assert (np.asarray(got) == np.asarray(want)).all()


@pytest.mark.parametrize("n,col_bytes,block", [
    (100, 800, 100), (128, 800, 128), (8704, 800, 128),
    (160, 32, 160),                 # fused QKV off the 128 grid: whole dim
    (384, None, 128),               # gated hidden on the grid
])
def test_pick_block_legal_dims(n, col_bytes, block):
    assert autotune.pick_block(n, col_bytes) == block


@pytest.mark.parametrize("n,col_bytes", [
    (8640, 800),            # bitnet-3b's FFN hidden: whole dim > VMEM
    (192, None),            # gated hidden: f is not its array's whole dim
])
def test_pick_block_refuses_dims_without_legal_block(n, col_bytes):
    """Mosaic takes a lane block only as a multiple of 128 or the whole
    dim: a dim off the 128 grid whose whole-dim block does not fit VMEM,
    or is not the array's whole dim, has no legal block."""
    with pytest.raises(ValueError, match="no legal column block"):
        autotune.pick_block(n, col_bytes)


# ---------------------------------------------------------------------------
# Autotune tiling matrix (DESIGN.md §Autotuning): every swept block shape
# is a pure tiling choice — bitwise the same oracle, under both arms
# ---------------------------------------------------------------------------

QLINEAR_TILINGS = [
    # (bm, bn, bkq, eg): single-pass barrier at small tiles; the two-pass
    # k-tiled barrier; k-tiling + expert grouping; whole-e group with
    # bkq == k (one k-tile, degenerate two-pass)
    (8, 128, 0, 1),
    (8, 384, 128, 1),
    (16, 384, 128, 2),
    (8, 128, 256, 4),
]


@pytest.mark.parametrize("impl", ARMS)
@pytest.mark.parametrize("bm,bn,bkq,eg", QLINEAR_TILINGS)
def test_qlinear_tiling_matrix_bitwise(bm, bn, bkq, eg, impl):
    """Every swept (bm, bn, bkq, eg) — including the two-pass k-tiled
    absmax barrier — dispatches bitwise-equal to the per-expert unfused
    oracle under an autotune.override, both arms."""
    e, c, k, n = 4, 6, 256, 384
    params = {"bm": bm, "bn": bn, "bkq": bkq, "eg": eg}
    assert autotune.valid_params(
        "qlinear", {"e": e, "m": c, "k": k, "n": n}, params)
    packed, scale = _expert_stack(e, k, n)
    x = jnp.asarray(rng.standard_normal((e, c, k)), jnp.float32)

    def per_expert(x):
        def one(xe, pe, se):
            tw = TernaryWeight(packed=pe, scale=1.0, shape=(k, n))
            xq = quantize(xe)
            acc = ops.ternary_matmul(xq.values, tw, impl="ref")
            return acc.astype(jnp.float32) * xq.scale * se.reshape(())
        return jax.vmap(one)(x, packed, scale)

    want = jax.jit(per_expert)(x)
    with autotune.override("qlinear", **params):
        got = jax.jit(lambda x: ops.qlinear_fused(x, packed, scale,
                                                  impl=impl))(x)
    assert (np.asarray(got) == np.asarray(want)).all()


FFN_TILINGS = [
    # (bm, bf, bn, bkq): default-ish; fine hidden tiles + k-tiled
    # barrier; coarse everything with bkq == k
    (8, 128, 128, 0),
    (8, 256, 128, 128),
    (16, 128, 256, 256),
]


@pytest.mark.parametrize("impl", ARMS)
@pytest.mark.parametrize("bm,bf,bn,bkq", FFN_TILINGS)
def test_ffn_tiling_matrix_bitwise(bm, bf, bn, bkq, impl):
    """Swept FFN tilings (incl. the k-tiled input barrier) == the
    three-dispatch unfused chain, bitwise, both arms."""
    d, f, m = 256, 256, 5
    params = {"bm": bm, "bf": bf, "bn": bn, "bkq": bkq}
    assert autotune.valid_params(
        "ffn", {"e": 1, "m": m, "k": d, "f": f, "n": d}, params)
    twu, twd = _node(d, f, 0.05), _node(f, d, 0.05)
    twg = _node(d, f, 0.05)

    def unfused(x):
        h = apply_act(_unfused(twg, x), "silu") * _unfused(twu, x)
        return _unfused(twd, h)

    gu_packed = jnp.concatenate([twg.packed, twu.packed], -1)
    gu_scale = jnp.concatenate(
        [jnp.broadcast_to(jnp.asarray(t.scale).reshape(1, 1), (1, f))
         for t in (twg, twu)], -1)
    x = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
    want = jax.jit(unfused)(x)
    with autotune.override("ffn", **params):
        got = jax.jit(lambda x: ops.ffn_fused(
            x, gu_packed, gu_scale, twd.packed,
            jnp.asarray(twd.scale).reshape(1, 1), gated=True, act="silu",
            impl=impl))(x)
    assert (np.asarray(got) == np.asarray(want)).all()


def test_both_arms_agree():
    """ref and pallas arms of the fused entries are interchangeable."""
    k, n = 256, 128
    tw = _node(k, n)
    sc = jnp.asarray(tw.scale).reshape(1, 1)
    x = jnp.asarray(rng.standard_normal((4, k)), jnp.float32)
    a = jax.jit(lambda x: ops.qlinear_fused(x, tw.packed, sc,
                                            impl="ref"))(x)
    b = jax.jit(lambda x: ops.qlinear_fused(x, tw.packed, sc,
                                            impl="pallas"))(x)
    assert (np.asarray(a) == np.asarray(b)).all()


# ---------------------------------------------------------------------------
# Engine-level: fused serving tree == legacy per-projection tree
# ---------------------------------------------------------------------------

def _logits_check(lf, ll):
    """Fused-vs-legacy logits equality, arm-aware.

    The ref arm (the production CPU dispatch) is bitwise. Under the
    interpret-mode pallas arm the FFN's transcendentals (exp inside
    silu/gelu) take shape-dependent SIMD paths on CPU — the in-kernel
    [bm, bf] tile vs the legacy [B, S, f] array — and repeated absmax
    requantization amplifies that 1-ulp drift into an occasional int8
    flip across layers (the knife-edge kernels/ref.py documents). There
    the contract is greedy-token equality plus tightly-close logits; the
    bitwise guarantee at the op level is pinned by the tests above.
    """
    import os
    arm = os.environ.get("REPRO_KERNEL_IMPL") or \
        ("pallas" if jax.default_backend() == "tpu" else "ref")
    a, b = np.asarray(lf), np.asarray(ll)
    if arm == "ref":
        assert (a == b).all()
    else:
        assert (np.argmax(a, -1) == np.argmax(b, -1)).all()
        np.testing.assert_allclose(a, b, atol=0.1, rtol=0.02)


@pytest.mark.parametrize("arch", ["bitnet-3b", "granite-moe-1b-a400m"])
def test_engine_fused_tree_matches_legacy(arch):
    """quantize_params(fuse=True) serves the same tokens (bitwise logits
    under the ref arm) as the legacy one-node-per-projection tree,
    through prefill AND decode — the end-to-end guarantee behind the
    dispatch-count drop."""
    from tests.test_models_smoke import _reduced
    from repro.models.transformer import init_params
    from repro.serving.engine import prefill, serve_step
    from repro.serving.quantize import quantize_params

    cfg = _reduced(arch)
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    qp = quantize_params(cfg, params)
    qp_legacy = quantize_params(cfg, params, fuse=False)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32)

    pf = jax.jit(lambda qp, t: prefill(cfg, qp, t, max_len=24))
    lf, cache_f = pf(qp, toks)
    ll, cache_l = pf(qp_legacy, toks)
    _logits_check(lf, ll)

    step = jax.jit(lambda qp, c, t: serve_step(cfg, qp, c, t))
    tok = jnp.argmax(lf, -1)[:, None].astype(jnp.int32)
    for _ in range(3):
        lf, cache_f = step(qp, cache_f, tok)
        ll, cache_l = step(qp_legacy, cache_l, tok)
        _logits_check(lf, ll)
        tok = jnp.argmax(lf, -1)[:, None].astype(jnp.int32)


def test_fused_qkv_node_layout():
    """quantize_params packs QKV codes [k//4, nq+2nkv] with per-segment
    per-column γ, and the whole-FFN node carries gate‖up + down streams."""
    from tests.test_models_smoke import _reduced
    from repro.models.transformer import init_params
    from repro.serving.quantize import quantize_params

    cfg = _reduced("bitnet-3b")
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    qp = quantize_params(cfg, params)
    wqkv = qp["layers"]["attn"]["wqkv"]
    n = cfg.q_dim + 2 * cfg.kv_dim
    assert wqkv["packed"].dtype == jnp.uint8
    assert wqkv["packed"].shape[-2:] == (cfg.d_model // 4, n)
    assert wqkv["scale"].shape[-2:] == (1, n)
    # each segment's γ row is constant (one scalar per code stream)
    seg = np.asarray(wqkv["scale"])[0]
    for lo, hi in ((0, cfg.q_dim), (cfg.q_dim, cfg.q_dim + cfg.kv_dim)):
        assert (seg[..., lo:hi] == seg[..., lo:lo + 1]).all()
    ffn = qp["layers"]["ffn"]
    assert ffn["gu_packed"].shape[-1] == 2 * cfg.d_ff
    assert ffn["down_packed"].shape[-2:] == (cfg.d_ff // 4, cfg.d_model)
