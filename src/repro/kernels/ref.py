"""Pure-jnp oracles for every Pallas kernel in this package."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.lop import features_to_pot, pot, unpack_features
from repro.core.ternary import unpack_ternary

NEG_INF = -1e30
INT32_MIN = jnp.iinfo(jnp.int32).min


def ternary_matmul_ref(x: jax.Array, packed: jax.Array,
                       k: int) -> jax.Array:
    """int8 x [m, k] @ packed-2bit ternary w [k//4, n] → int32 [m, n]."""
    w = unpack_ternary(packed, k)
    return jax.lax.dot(x, w, preferred_element_type=jnp.int32)


def _unpack_any(packed: jax.Array, k: int) -> jax.Array:
    """unpack_ternary over optional leading (expert/layer) dims."""
    if packed.ndim == 2:
        return unpack_ternary(packed, k)
    lead = packed.shape[:-2]
    flat = packed.reshape((-1,) + packed.shape[-2:])
    w = jax.vmap(lambda p: unpack_ternary(p, k))(flat)
    return w.reshape(lead + (k, packed.shape[-1]))


def qlinear_ref(x, packed, scale, bias=None, *, act=None):
    """Oracle of the fused TINT projection (kernels/qlinear.fused_qlinear).

    The unfused chain written out: absmax barrier → integer GEMM →
    dequant by (x-scale · per-column γ) → bias → activation. x f32/bf16
    [..., k] with packed [k//4, n], or the grouped-expert form x
    [E, C, k] with packed [E, k//4, n] — the latter replaces the
    per-expert vmap with one batched contraction. scale f32 [..., 1, n]
    per-column γ row. → f32 [..., n].
    """
    from repro.core.quantization import quantize
    from repro.kernels.qlinear import apply_act

    k = packed.shape[-2] * 4
    xq = quantize(x)
    w = _unpack_any(packed, k)
    if packed.ndim == 2:
        acc = jax.lax.dot_general(
            xq.values, w,
            dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
    else:
        acc = jnp.einsum("eck,ekn->ecn", xq.values, w,
                         preferred_element_type=jnp.int32)
    y = acc.astype(jnp.float32) * xq.scale * scale
    if bias is not None:
        y = y + bias
    return apply_act(y, act)


def ffn_fused_ref(x, gu_packed, gu_scale, down_packed, down_scale, *,
                  gated: bool, act: str):
    """Oracle of the one-launch FFN (kernels/qlinear.fused_ffn).

    h = act(x·Wg)·(x·Wu) (or act(x·Wu) ungated), then the hidden vector
    crosses its own absmax barrier before the down projection — exactly
    the unfused silu(qlinear(g,x))·qlinear(u,x) → qlinear(d,h) chain.
    """
    from repro.kernels.qlinear import apply_act

    f = down_packed.shape[-2] * 4
    h_all = qlinear_ref(x, gu_packed, gu_scale)
    if gated:
        h = apply_act(h_all[..., :f], act) * h_all[..., f:]
    else:
        h = apply_act(h_all, act)
    return qlinear_ref(h, down_packed, down_scale)


def lop_scores_ref(q_pot: jax.Array, packed_feat: jax.Array) -> jax.Array:
    """Surrogate scores from the packed feature cache.

    q_pot int8 [g, d] (already pot-rounded); packed_feat uint8 [m, d//2]
    → int32 [g, m].
    """
    kp = features_to_pot(unpack_features(packed_feat))       # [m, d] int8
    return jax.lax.dot(q_pot, kp.T, preferred_element_type=jnp.int32)


def flash_prefill_ref(q, k, v, q_scale, k_scale, v_scale, *,
                      softmax_scale: float, causal: bool = True,
                      window: int = 0) -> jax.Array:
    """Dense (causal/SWA) int8 attention oracle with per-token absmax scales.

    q/k/v int8 [s, d]; scales f32 [s, 1] → f32 [s, d].
    """
    s = q.shape[0]
    logits = jax.lax.dot(q, k.T,
                         preferred_element_type=jnp.int32).astype(jnp.float32)
    logits = logits * q_scale * k_scale.reshape(1, s) * softmax_scale
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    if causal:
        logits = jnp.where(qpos >= kpos, logits, NEG_INF)
        if window:
            logits = jnp.where(qpos - kpos < window, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.dot(p, v.astype(jnp.float32) * v_scale)


def prefill_attention_ref(qi, qsc, k_cache, v_cache, k_scale, v_scale,
                          kv_len, q_off=0, *, causal: bool = True,
                          window: int = 0, softmax_scale: float,
                          int8_logits: bool = False,
                          chunk: int = 256) -> jax.Array:
    """Batched GQA prefill-chunk attention oracle (streamed over q chunks).

    qi int8 [B, H, C, dh]; qsc f32 [B, H, C]; caches int8/f32
    [B, Hkv, M, ...]; kv_len int32 [B]; ``q_off`` (scalar, may be traced)
    is the global position of query column 0. → f32 [B, H, C, dh].

    Per query row the masked logits, the guarded softmax and the
    normalization are independent of C and of every other row, so running
    a prompt through this oracle in chunks against the same
    capacity-padded cache is *bitwise* identical to one whole-prompt call
    — the invariant the chunked scheduler's token-exactness rests on
    (DESIGN.md §Chunked-prefill). ``int8_logits`` keeps QKᵀ in the
    integer domain (int8×int8→int32, BoothFlex-faithful); the default
    dequantizes K once and streams f32 MXU dots. The inner scan over
    query chunks (``REPRO_ATTN_CHUNK`` raises it for accounting probes)
    bounds the materialized logits to [B, H, chunk, M] at dry-run shapes.
    """
    import os

    from repro.distributed.partitioning import shard
    from repro.models.attention import _model_axis_size
    from repro.models.scan_utils import accounting_unroll

    b, h, sq, dh = qi.shape
    hkv, m = k_cache.shape[1], k_cache.shape[2]
    chunk = min(int(os.environ.get("REPRO_ATTN_CHUNK", chunk)), sq)
    if hkv != h:
        rep = h // hkv
        # repeat K/V to the flat H dim so TP head sharding survives (see
        # models/attention.py); with non-divisible H the q chunks SP-shard
        k_cache = jnp.repeat(k_cache, rep, axis=1)
        v_cache = jnp.repeat(v_cache, rep, axis=1)
        k_scale = jnp.repeat(k_scale, rep, axis=1)
        v_scale = jnp.repeat(v_scale, rep, axis=1)
    head_sharded = h % _model_axis_size() == 0

    pad = (-sq) % chunk
    if pad:
        qi = jnp.pad(qi, ((0, 0), (0, 0), (0, pad), (0, 0)))
        qsc = jnp.pad(qsc, ((0, 0), (0, 0), (0, pad)))
    nc = qi.shape[2] // chunk
    qg = qi.reshape(b, h, nc, chunk, dh).transpose(2, 0, 1, 3, 4)
    qsg = qsc.reshape(b, h, nc, chunk).transpose(2, 0, 1, 3)

    kpos = jnp.arange(m)
    vf = v_cache.astype(jnp.float32) * v_scale[..., None]
    # Both QKᵀ branches dequantize AFTER the dot: int8 products summed in
    # f32 stay exact below 2²⁴ (|s| ≤ 127²·dh), so the f32 branch is
    # bitwise identical to the int32 branch on CPU — the flag only picks
    # the MXU datapath (int8 2× throughput) on real TPUs. Scaling before
    # the dot would differ at ~1e-7, which repeated absmax requantization
    # across layers can amplify into a rounding flip (knife-edge).
    kk = k_cache if int8_logits else k_cache.astype(jnp.float32)
    if head_sharded:
        kk = shard(kk, "dp", "tp", None, None)
        vf = shard(vf, "dp", "tp", None, None)

    def body(_, args):
        qc, qsc_c, ci = args                             # [B, H, C, dh]
        if head_sharded:
            qc = shard(qc, "dp", "tp", None, None)
        else:
            qc = shard(qc, "dp", None, "sp", None)
        if int8_logits:
            s = jnp.einsum("bhcd,bhmd->bhcm", qc, kk,
                           preferred_element_type=jnp.int32)
            s = s.astype(jnp.float32)
        else:
            s = jnp.einsum("bhcd,bhmd->bhcm", qc.astype(jnp.float32), kk,
                           preferred_element_type=jnp.float32)
        s = s * k_scale[:, :, None, :] * qsc_c[..., None] * softmax_scale
        qpos = q_off + ci * chunk + jnp.arange(chunk)
        mask = kpos[None, None, :] < kv_len[:, None, None]   # [B, C, M]
        if causal:
            mask &= qpos[None, :, None] >= kpos[None, None, :]
            if window:
                mask &= (qpos[None, :, None] - kpos[None, None, :]) < window
        s = jnp.where(mask[:, None], s, NEG_INF)
        # guarded softmax: fully-masked rows emit exact zero, matching the
        # kernel's ℓ > 0 flush guard
        mx = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - jnp.maximum(mx, -1e29))
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhcm,bhmd->bhcd", p, vf)
        return None, o / jnp.where(l > 0, l, 1.0)

    _, oc = jax.lax.scan(body, None, (qg, qsg, jnp.arange(nc)),
                         unroll=accounting_unroll(nc))
    o = oc.transpose(1, 2, 0, 3, 4).reshape(b, h, nc * chunk, dh)
    return o[:, :, :sq]


def sparse_decode_attention_ref(q, k_cache, v_cache, q_scale, k_scale,
                                v_scale, block_idx, gate_tokens, *,
                                block: int, softmax_scale: float) -> jax.Array:
    """Block-sparse decode attention oracle (mirrors the kernel contract).

    q int8 [g, d]; caches int8 [m, d]; scales f32 per the kernel;
    block_idx int32 [nb]; gate_tokens int32 [3*nb] = [gate ‖ end ‖ start].
    Exact softmax over the union of gated, in-interval tokens.
    """
    m, d = k_cache.shape
    nb = block_idx.shape[0]
    gate = gate_tokens[:nb] > 0
    end = gate_tokens[nb:2 * nb]
    start = gate_tokens[2 * nb:]
    kb = k_cache.reshape(m // block, block, d)
    vb = v_cache.reshape(m // block, block, d)
    ksb = k_scale.reshape(m // block, block, 1)
    vsb = v_scale.reshape(m // block, block, 1)
    k_sel = kb[block_idx].reshape(nb * block, d)
    v_sel = vb[block_idx].reshape(nb * block, d)
    ks_sel = ksb[block_idx].reshape(nb * block, 1)
    vs_sel = vsb[block_idx].reshape(nb * block, 1)
    t = jnp.arange(block)[None, :]
    tok_in = (t >= start[:, None]) & (t < end[:, None])
    valid = (tok_in & gate[:, None]).reshape(nb * block)

    logits = jax.lax.dot(q, k_sel.T,
                         preferred_element_type=jnp.int32).astype(jnp.float32)
    logits = logits * q_scale * ks_sel.reshape(1, -1) * softmax_scale
    logits = jnp.where(valid[None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.dot(p, v_sel.astype(jnp.float32) * vs_sel)


# ---------------------------------------------------------------------------
# Fused batched decode attention (oracle for kernels/decode_attention.py)
# ---------------------------------------------------------------------------

def _gather_blocks(arr, idx, block):
    """arr [B,Hkv,M,...] , idx [B,Hkv,G',K] → [B,Hkv,G',K·block,...]."""
    b, hkv, m = arr.shape[:3]
    k = idx.shape[-1]
    blocks = arr.reshape(b, hkv, m // block, block, *arr.shape[3:])

    def per_bh(blocks_bh, idx_bh):                       # [NB,block,...],[G,K]
        return blocks_bh[idx_bh]                         # [G,K,block,...]

    out = jax.vmap(jax.vmap(per_bh))(blocks, idx)
    return out.reshape(b, hkv, idx.shape[2], k * block, *arr.shape[3:])


def _stats_to_out(m, l, acc, b, h, dh, return_stats):
    out = (acc / jnp.where(l > 0, l, 1.0)).reshape(b, h, acc.shape[-1])
    if return_stats:
        return out, m.reshape(b, h, 1), l.reshape(b, h, 1)
    return out


def decode_attention_ref(qi, qsc, k_cache, v_cache, k_scale, v_scale, feat,
                         new_len, *, block: int, k_keep: int, window: int,
                         softmax_scale: float, use_lop: bool = True,
                         shared_select: bool = False, pos_offset=None,
                         return_stats: bool = False):
    """Batched decode-attention oracle (screen → select → exact, or dense).

    qi int8 [B,H,dh]; qsc f32 [B,H,1]; caches int8/f32 [B,Hkv,M,...];
    feat uint8 [B,Hkv,M,dh//2]; new_len int32 [B] (0 = retired lane —
    those rows emit exactly zero); ``pos_offset`` maps cache row 0 to a
    global token position (SP shards; must be block-aligned).
    → f32 [B,H,dh]; with ``return_stats`` also the unnormalized softmax
    (m, ℓ) f32 [B,H,1] for the flash-decoding shard merge.
    """
    from repro.serving.lop_select import select_blocks, token_valid_mask

    b, h, dh = qi.shape
    hkv, m = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    po = 0 if pos_offset is None else pos_offset
    qg = qi.reshape(b, hkv, g, dh)
    qs = qsc.reshape(b, hkv, g, 1)

    if not use_lop:
        s = jnp.einsum("bhgd,bhmd->bhgm", qg, k_cache,
                       preferred_element_type=jnp.int32).astype(jnp.float32)
        s = s * qs * k_scale[:, :, None, :] * softmax_scale
        valid = token_valid_mask(m, new_len, window, pos_offset=po)
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        mx = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - jnp.maximum(mx, -1e29))
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        l = jnp.sum(p, axis=-1, keepdims=True)
        vf = v_cache.astype(jnp.float32) * v_scale[..., None]
        acc = jnp.einsum("bhgm,bhmd->bhgd", p, vf)
        return _stats_to_out(mx, l, acc, b, h, dh, return_stats)

    # 1./2. screen over the packed feature cache + comparison-free top-K
    kp = features_to_pot(unpack_features(feat))          # [B,Hkv,M,dh] int8
    scores = jnp.einsum("bhgd,bhmd->bhgm", pot(qg), kp,
                        preferred_element_type=jnp.int32)
    if shared_select:
        scores = jnp.max(scores, axis=2, keepdims=True)  # [B,Hkv,1,M]
    idx, gate_tokens = select_blocks(scores, new_len, block=block,
                                     k_keep=k_keep, window=window,
                                     block_offset=po // block)

    # 3./4. gather the candidate blocks + exact masked attention stats
    gsel = idx.shape[2]
    k_sel = _gather_blocks(k_cache, idx, block)          # [B,Hkv,G',K·bl,dh]
    v_sel = _gather_blocks(v_cache, idx, block)
    ks_sel = _gather_blocks(k_scale, idx, block)         # [B,Hkv,G',K·bl]
    vs_sel = _gather_blocks(v_scale, idx, block)

    if gsel == 1:
        s = jnp.einsum("bhgd,bhkd->bhgk", qg, k_sel[:, :, 0],
                       preferred_element_type=jnp.int32).astype(jnp.float32)
        s = s * qs * ks_sel[:, :, 0][:, :, None] * softmax_scale
    else:
        s = jnp.einsum("bhgd,bhgkd->bhgk", qg, k_sel,
                       preferred_element_type=jnp.int32).astype(jnp.float32)
        s = s * qs * ks_sel * softmax_scale

    kk = idx.shape[-1]
    gate = gate_tokens[..., :kk] > 0                     # [B,Hkv,G',K]
    end = gate_tokens[..., kk:2 * kk]
    start = gate_tokens[..., 2 * kk:]
    t = jnp.arange(block)[None, None, None, None, :]
    live = ((t >= start[..., None]) & (t < end[..., None])
            & gate[..., None])                           # [B,Hkv,G',K,block]
    live = live.reshape(b, hkv, gsel, kk * block)        # broadcasts G'=1
    s = jnp.where(live, s, NEG_INF)

    mx = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.maximum(mx, -1e29))
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1, keepdims=True)
    vf = v_sel.astype(jnp.float32) * vs_sel[..., None]
    if gsel == 1:
        acc = jnp.einsum("bhgk,bhkd->bhgd", p, vf[:, :, 0])
    else:
        acc = jnp.einsum("bhgk,bhgkd->bhgd", p, vf)
    return _stats_to_out(mx, l, acc, b, h, dh, return_stats)
