"""Serving engine: prefill + one-token decode for every family.

This is the paper's full pipeline on TPU terms (DESIGN.md §2):

  prefill   — BitLinear projections (TINT) → rope → absmax barrier → ONE
              fused attention dispatch (:func:`repro.kernels.ops.
              prefill_attention`): batched causal int8 flash attention
              over the capacity-padded cache with f32 online-softmax
              carry; K/V/LOP-feature cache written per layer. Two entry
              shapes share the op: :func:`prefill` (whole prompt) and
              :func:`prefill_chunk` (one fixed-size chunk of a prompt
              against the cache written so far — the chunked-prefill
              tentpole, DESIGN.md §Chunked-prefill), bit-identical per
              query row by construction.
  decode    — one token: project/rope/quantize, append to cache, then ONE
              fused attention dispatch (:func:`repro.kernels.ops.
              decode_attention`): the LOP screen over the 4-bit feature
              cache, the comparison-free block top-K, and exact int8
              attention over the K candidate blocks run as a single
              batched head-pipelined kernel spanning every (batch,
              kv-head) lane — then BitLinear FFN/MoE.

Projections dispatch through the fused TINT entries (DESIGN.md
§TINT-projection-fusion): a decoder layer's non-attention hot path is
THREE dispatches — fused QKV (one packed weight, per-column dequant),
the O projection, and the whole FFN (gate·up → in-VMEM re-barrier →
down) — each running the absmax barrier, the packed-ternary GEMM and
the epilogue inside one kernel, so no f32 activation or int32
accumulator round-trips HBM between them. MoE layers run every
expert's FFN as ONE grouped dispatch (expert = grid axis). The fused
entry owns the barrier dtype: attention outputs feed ``qlinear``
directly, with no caller-side ``astype`` re-cast.

Attention-free layers (Mamba/RWKV) carry recurrent state instead. With an
active mesh the decode attention runs the SP quota-sharded core
(:mod:`repro.distributed.sp_decode`) — the cache's token axis lives sharded
across the model axis; each shard calls the same fused kernel with its
``pos_offset`` and softmax stats merge flash-decoding style.

Beyond-paper decode variants (group-shared selection, integer-domain
prefill logits) are ``ModelConfig`` fields pinned once per entry call by
:func:`repro.configs.base.resolve_decode_flags`; the legacy
``REPRO_GQA_SHARED_SELECT`` / ``REPRO_INT8_LOGITS`` env flags remain as
fallbacks for unset fields.

Latent attention (MLA, ``cfg.mla``) projects each token to one latent
row c ‖ k_rope, cached int8 with one scale (``k``/``k_scale``/``feat``,
one lane shared by every head), and attends in the *absorbed* form in
prefill and decode alike: per head the query q̃ = (q_nope·W_UKᵀ) ‖ q_rope
scores the latent rows directly, the attention-weighted latent rows'
c part is the value, and W_UV maps it back per head before o_proj
(DESIGN.md §Latent-attention-and-dropless-experts). The absorption, the
attention launches and W_UV are marked ``mla_attention`` in a profiler
trace (:mod:`repro.core.marks`); o_proj is not.

Leading dense layers (``first_k_dense``) are a stack of their own,
``dense_layers``, scanned before ``layers`` with their own cache stack,
so no step slices or re-joins the pool.

Slot-paged decode: when the cache carries a per-lane ``active`` mask (a
:func:`repro.serving.cache.init_cache_pool` pool), ``serve_step`` decodes
only the live lanes — inactive lanes are screened out of the LOP selection
(effective length 0), skipped by the cache append, emit zero attention
output, and keep their ``lengths`` frozen. This is what lets the scheduler
admit/retire individual requests mid-flight without recompiling the step.

These functions are the compute layer under the typed serving API
(DESIGN.md §Serving-API): :class:`repro.serving.api.PooledEngine` wraps
them behind the ``InferenceEngine`` protocol, fusing ``serve_step`` with
the per-lane batched sampler into one jitted decode+sample dispatch; the
scheduler and drivers never call these entry points directly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import resolve_decode_flags
from repro.core.lop import lop_features, pack_features
from repro.core.marks import scope
from repro.core.qlinear import qlinear, qlinear_expert, qlinear_split
from repro.core.quantization import quantize
from repro.distributed.partitioning import current_mesh, shard
from repro.kernels import ops
from repro.models import rwkv6
from repro.models.layers import (embedding_apply, head_apply,
                                 mla_softmax_scale, norm_apply, rope,
                                 rope_freqs)
from repro.models.mamba import mamba_decode_step, mamba_forward
from repro.models.moe import ffn_apply, moe_apply
from repro.serving.cache import attn_cache_zeros, init_cache, round_up
from repro.serving.lop_select import k_keep_blocks

NEG_INF = -1e30


def _layer_scan(body, x, xs):
    """Layer-stack scan with dry-run accounting unroll."""
    from repro.models.scan_utils import accounting_unroll
    length = jax.tree.leaves(xs)[0].shape[0]
    return jax.lax.scan(body, x, xs, unroll=accounting_unroll(length))


def _stacks(qp) -> list:
    """The decoder layer stacks in order: the leading dense layers (a
    stack of their own, ``first_k_dense``) and then ``layers``."""
    return [n for n in ("dense_layers", "layers") if n in qp]


def _q(x, axis=-1):
    qt = quantize(x, axis=axis)
    return qt.values, qt.scale


def _norm(params, x, kind: str):
    """``norm_apply`` with RMSNorm through the row-invariant
    :func:`repro.kernels.ops.rmsnorm` (whole prompt, prefill chunk and
    decode lanes agree row for row)."""
    if kind == "rmsnorm":
        return ops.rmsnorm(x, params["g"])
    return norm_apply(params, x, kind)


def _shard_batch(x, *rest):
    """Constrain batch over dp only when it divides (long_500k has B=1)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    dp = int(mesh.shape.get("data", 1)) * int(mesh.shape.get("pod", 1))
    if x.shape[0] % dp == 0:
        return shard(x, "dp", *rest)
    return x


# ===========================================================================
# Attention layer — prefill (whole prompt and chunked, one fused dispatch)
# ===========================================================================

def _project_qkv(cfg, lp, h, src=None):
    b, s, _ = h.shape
    src = h if src is None else src
    skv = src.shape[1]
    if "wqkv" in lp:
        # fused-at-deployment QKV: ONE dispatch (barrier + ternary GEMM +
        # per-column dequant inside the kernel), split is a free view
        q, k, v = qlinear_split(lp["wqkv"], h,
                                (cfg.q_dim, cfg.kv_dim, cfg.kv_dim))
    else:
        q = qlinear(lp["wq"], h)
        k = qlinear(lp["wk"], src)
        v = qlinear(lp["wv"], src)
    q = q.reshape(b, s, cfg.n_heads, cfg.hd)
    k = k.reshape(b, skv, cfg.n_kv_heads, cfg.hd)
    v = v.reshape(b, skv, cfg.n_kv_heads, cfg.hd)
    return q, k, v


def _quantize_kv(k, v):
    """[B, S, Hkv, dh] f32 → int8 caches in [B, Hkv, S, ...] layout."""
    ki, ksc = _q(k)
    vi, vsc = _q(v)
    ki = ki.transpose(0, 2, 1, 3)
    vi = vi.transpose(0, 2, 1, 3)
    ksc = ksc[..., 0].transpose(0, 2, 1)
    vsc = vsc[..., 0].transpose(0, 2, 1)
    feat = pack_features(lop_features(ki))
    return ki, vi, ksc, vsc, feat


def _pad_cache(arr, cap: int, axis: int = 2):
    pad = [(0, 0)] * arr.ndim
    pad[axis] = (0, cap - arr.shape[axis])
    return jnp.pad(arr, pad)


def attn_prefill(cfg, lp, h, *, capacity: int):
    """→ (attn_out [B,S,D], cache_layer). Caches K/V/features at [0, S).

    The whole prompt is one maximal chunk: K/V/features are written into
    the capacity-padded cache first and attention runs over THAT cache
    through :func:`repro.kernels.ops.prefill_attention` (``q_offset=0``,
    ``kv_len=S``) — the same op, operand shapes and masking as
    :func:`attn_prefill_chunk`, which is what makes chunked prefill
    bit-identical per query row (DESIGN.md §Chunked-prefill).
    """
    b, s, _ = h.shape
    if cfg.mla:
        return mla_prefill_chunk(cfg, lp, h,
                                 attn_cache_zeros(cfg, b, capacity),
                                 start=0, kv_len=jnp.full((b,), s, jnp.int32))
    q, k, v = _project_qkv(cfg, lp, h)
    positions = jnp.arange(s)[None, :]
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    qi, qsc = _q(q)
    ki, vi, ksc, vsc, feat = _quantize_kv(k, v)
    qi = qi.transpose(0, 2, 1, 3)                        # [B, H, S, dh]
    qsc = qsc[..., 0].transpose(0, 2, 1)

    cache_l = {
        "k": _pad_cache(ki, capacity), "v": _pad_cache(vi, capacity),
        "k_scale": _pad_cache(ksc, capacity), "v_scale": _pad_cache(vsc,
                                                                    capacity),
        "feat": _pad_cache(feat, capacity),
    }
    # the chunked families must attend the FULL capacity so chunked rows
    # (which see the pool lane at capacity) stay bitwise equal;
    # run-to-completion families (hybrid/encdec self-attn) never chunk,
    # so their attention view trims to the prompt's block roundup — cost
    # scales with S, not pool capacity
    m_att = capacity if cfg.family in ("dense", "moe", "vlm") \
        else min(capacity, round_up(s, cfg.lop_block))
    o = ops.prefill_attention(
        qi, qsc, cache_l["k"][:, :, :m_att], cache_l["v"][:, :, :m_att],
        cache_l["k_scale"][:, :, :m_att], cache_l["v_scale"][:, :, :m_att],
        jnp.full((b,), s, jnp.int32), causal=True,
        window=cfg.swa_window, int8_logits=bool(cfg.int8_logits))
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.q_dim)
    out = qlinear(lp["wo"], o)
    return out, cache_l


def _kv_rows(ki, vi, ksc, vsc, feat):
    """The cache leaves of quantized K/V rows, by leaf name."""
    return {"k": ki, "v": vi, "k_scale": ksc, "v_scale": vsc, "feat": feat}


def _write_chunk(cl, rows, start):
    """Write a C-token quantized chunk into the cache at [start, start+C).

    ``rows`` holds each leaf's C new rows in the cache layout ([B, Hkv,
    C, ...]). One ``dynamic_update_slice`` per leaf at the (possibly
    traced) chunk start — the cache-pool analogue of the per-token
    ``_write_token``. Padded tail tokens of a final chunk land here too;
    they sit above ``lengths`` and are stale-masked like every other
    dead byte (DESIGN.md §Chunked-prefill partial-insert invariants).
    """
    def wr(arr, val):
        return jax.lax.dynamic_update_slice(
            arr, val, (0, 0, start) + (0,) * (arr.ndim - 3))

    cl = dict(cl)
    for name, val in rows.items():
        cl[name] = wr(cl[name], val)
    return cl


def attn_prefill_chunk(cfg, lp, h, cl, *, start, kv_len):
    """One C-token prefill chunk against an existing cache layer.

    h [B, C, D] are the chunk's hidden states at global positions
    [start, start+C); ``cl`` holds every earlier chunk's K/V at
    [0, start). The chunk's quantized K/V/features are written at
    [start, start+C) and its queries attend causally over [0, kv_len)
    through the same fused dispatch as :func:`attn_prefill` — the
    chunk-carry is the cache itself plus (start, kv_len); no softmax
    state crosses chunk boundaries (it lives in the kernel's VMEM
    scratch within one call).
    """
    if cfg.mla:
        return mla_prefill_chunk(cfg, lp, h, cl, start=start, kv_len=kv_len)
    b, c, _ = h.shape
    q, k, v = _project_qkv(cfg, lp, h)
    positions = start + jnp.arange(c)[None, :]
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    qi, qsc = _q(q)
    ki, vi, ksc, vsc, feat = _quantize_kv(k, v)
    qi = qi.transpose(0, 2, 1, 3)                        # [B, H, C, dh]
    qsc = qsc[..., 0].transpose(0, 2, 1)

    cl = _write_chunk(cl, _kv_rows(ki, vi, ksc, vsc, feat), start)
    o = ops.prefill_attention(
        qi, qsc, cl["k"], cl["v"], cl["k_scale"], cl["v_scale"], kv_len,
        q_offset=start, causal=True, window=cfg.swa_window,
        int8_logits=bool(cfg.int8_logits))
    o = o.transpose(0, 2, 1, 3).reshape(b, c, cfg.q_dim)
    out = qlinear(lp["wo"], o)
    return out, cl


def build_cross_cache(cfg, lp, enc, capacity: int):
    """Quantize encoder memory through this layer's K/V projections.

    A fused ``wkv`` node (quantize-time KV fusion for cross-attention —
    both consume the encoder memory) projects K and V in one dispatch.
    """
    b, s, _ = enc.shape
    if "wkv" in lp:
        k, v = qlinear_split(lp["wkv"], enc, (cfg.kv_dim, cfg.kv_dim))
    else:
        k = qlinear(lp["wk"], enc)
        v = qlinear(lp["wv"], enc)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.hd)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.hd)
    ki, vi, ksc, vsc, feat = _quantize_kv(k, v)
    return {
        "k": _pad_cache(ki, capacity), "v": _pad_cache(vi, capacity),
        "k_scale": _pad_cache(ksc, capacity),
        "v_scale": _pad_cache(vsc, capacity),
        "feat": _pad_cache(feat, capacity),
    }


def cross_attn_prefill(cfg, lp, h, cross_cache, cross_len, kv_max=None):
    """Decoder-side cross attention over a prequantized encoder cache.

    ``kv_max`` (static) trims the attention view of the cross cache to
    the encoder length's block roundup — encdec never chunks, so the
    cost scales with the actual frames, not ``cross_ctx`` capacity.
    """
    b, s, _ = h.shape
    m = kv_max or cross_cache["k"].shape[2]
    q = qlinear(lp["wq"], h).reshape(b, s, cfg.n_heads, cfg.hd)
    qi, qsc = _q(q)
    qi = qi.transpose(0, 2, 1, 3)
    qsc = qsc[..., 0].transpose(0, 2, 1)
    o = ops.prefill_attention(
        qi, qsc, cross_cache["k"][:, :, :m], cross_cache["v"][:, :, :m],
        cross_cache["k_scale"][:, :, :m], cross_cache["v_scale"][:, :, :m],
        cross_len, causal=False, int8_logits=bool(cfg.int8_logits))
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.q_dim)
    return qlinear(lp["wo"], o)


# ===========================================================================
# Attention layer — decode (LOP sparse / dense baseline / SP-sharded)
# ===========================================================================

def lop_decode_attention(cfg, qi, qsc, cl, new_len, *, window: int,
                         use_lop: bool = True, k_keep: int | None = None):
    """Local (non-SP) decode attention core — one fused-kernel dispatch.

    qi int8 [B, H, dh]; qsc f32 [B, H, 1]; cl = cache layer; new_len [B].
    → f32 [B, H, dh].

    The dense baseline, the LOP screen → comparison-free block top-K →
    exact candidate attention, and group-shared selection all route
    through :func:`repro.kernels.ops.decode_attention`: one batched
    kernel whose grid spans every (batch, kv-head) lane, replacing the
    per-head ``lop_screen``/``sparse_decode`` small-kernel dispatch under
    a triple ``vmap`` (DESIGN.md §Fused-decode-kernel). Retired slot-pool
    lanes arrive with ``new_len == 0`` and emit exactly zero.

    ``k_keep`` overrides the config's kept-block budget — the speculative
    draft pass degrades the screen to a smaller K than serving decode
    uses (DESIGN.md §Speculative-decoding); ``None`` keeps the config
    policy.
    """
    cfg = resolve_decode_flags(cfg)
    m = cl["k"].shape[2]
    if k_keep is None:
        k_keep = k_keep_blocks(cfg, m)
    # latent attention: the values are the latent rows' c part
    latent = {"v_cols": cfg.kv_lora_rank,
              "softmax_scale": mla_softmax_scale(cfg)} if cfg.mla else {}
    return ops.decode_attention(
        qi, qsc, cl["k"], cl.get("v"), cl["k_scale"], cl.get("v_scale"),
        cl["feat"], new_len, block=cfg.lop_block,
        k_keep=max(1, min(k_keep, m // cfg.lop_block)),
        window=window, use_lop=use_lop,
        shared_select=bool(cfg.gqa_shared_select), **latent)


def _write_token(cl, rows, lengths, active=None):
    """Append one quantized token per sequence at its own position.

    ``rows`` holds each leaf's new row per lane ([B, Hkv, ...]). With
    ``active`` given, retired/empty slots keep their lane untouched (the
    write is computed and discarded — branch-free under vmap).
    """
    ok = jnp.ones_like(lengths, bool) if active is None else active

    def wr(arr, val, pos, ok_):
        # arr [Hkv, M, ...]; val [Hkv, ...]
        upd = jax.lax.dynamic_update_slice(
            arr, val[:, None], (0, pos) + (0,) * (arr.ndim - 2))
        return jnp.where(ok_, upd, arr)

    cl = dict(cl)
    for name, val in rows.items():
        cl[name] = jax.vmap(wr)(cl[name], val, lengths, ok)
    return cl


def attn_decode(cfg, lp, h, cl, lengths, *, use_lop=True, sp_axes=None,
                active=None, k_keep=None):
    """One-token self-attention with cache append. h [B, 1, D].

    ``active`` [B] bool masks slot-paged lanes: inactive lanes get effective
    length 0 (nothing valid for the LOP screen / block top-K), no cache
    write, and zero attention output. ``k_keep`` degrades the LOP
    selection budget (speculative draft pass); ``None`` = config policy.
    """
    if cfg.mla:
        if sp_axes:
            raise NotImplementedError("latent attention has no "
                                      "sequence-sharded decode")
        return mla_decode(cfg, lp, h, cl, lengths, use_lop=use_lop,
                          active=active, k_keep=k_keep)
    b = h.shape[0]
    q, k, v = _project_qkv(cfg, lp, h)
    positions = lengths[:, None]
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    qi, qsc = _q(q[:, 0])                                # [B, H, dh]
    ki, ksc = _q(k[:, 0])                                # [B, Hkv, dh]
    vi, vsc = _q(v[:, 0])
    feat = pack_features(lop_features(ki))
    new_len = lengths + 1
    if active is not None:
        new_len = jnp.where(active, new_len, 0)

    if sp_axes:
        from repro.distributed.sp_decode import sp_decode_attention
        out, cl = sp_decode_attention(
            cfg, qi, qsc, ki, vi, ksc, vsc, feat, cl, lengths,
            window=cfg.swa_window, use_lop=use_lop and cfg.use_lop,
            sp_axes=sp_axes, active=active)
    else:
        cl = _write_token(cl, _kv_rows(ki, vi, ksc[..., 0], vsc[..., 0],
                                       feat), lengths, active)
        out = lop_decode_attention(cfg, qi, qsc, cl, new_len,
                                   window=cfg.swa_window,
                                   use_lop=use_lop and cfg.use_lop,
                                   k_keep=k_keep)
    if active is not None:
        out = jnp.where(active[:, None, None], out, 0.0)
    out = qlinear(lp["wo"], out.reshape(b, 1, cfg.q_dim))
    return out, cl


def cross_attn_decode(cfg, lp, h, cross_cl, cross_len, *, use_lop=True,
                      sp_axes=None):
    """One-token cross-attention (no cache write)."""
    b = h.shape[0]
    q = qlinear(lp["wq"], h).reshape(b, cfg.n_heads, cfg.hd)
    qi, qsc = _q(q)
    if sp_axes:
        from repro.distributed.sp_decode import sp_decode_attention
        out, _ = sp_decode_attention(
            cfg, qi, qsc, None, None, None, None, None, cross_cl, cross_len,
            window=0, use_lop=use_lop and cfg.use_lop, sp_axes=sp_axes,
            write=False)
    else:
        out = lop_decode_attention(cfg, qi, qsc, cross_cl, cross_len,
                                   window=0, use_lop=use_lop and cfg.use_lop)
    return qlinear(lp["wo"], out.reshape(b, 1, cfg.q_dim))


# ===========================================================================
# Latent attention (MLA) — one cached int8 row per token, absorbed form
# ===========================================================================

def _mla_latent(cfg, lp, h, positions):
    """Project h [B, S, D] → (q_nope [B, S, H, dn], q_rope [B, S, H, dr]
    rotated, the cache rows of the latent c ‖ k_rope by leaf name, in
    the cache layout [B, 1, S, ...]). q and kv_a share one dispatch (one
    barrier on h); c takes kv_a's RMSNorm, k_rope the rotary, and the
    row is stored int8 with one absmax scale and its LOP features."""
    dn, dc, qd = cfg.qk_nope_head_dim, cfg.kv_lora_rank, cfg.mla_q_dim
    b, s, _ = h.shape
    y = qlinear(lp["wq_kva"], h)        # zero columns pad it to 128s
    q = y[..., :qd].reshape(b, s, cfg.n_heads, -1)
    kva = y[..., qd:qd + cfg.latent_dim]
    freqs = rope_freqs(cfg)
    q_rope = rope(q[..., dn:], positions, cfg.rope_theta, freqs)
    k_rope = rope(kva[:, :, None, dc:], positions, cfg.rope_theta, freqs)
    c = ops.rmsnorm(kva[..., :dc], lp["kv_norm"]["g"])
    li, lsc = _q(jnp.concatenate([c, k_rope[:, :, 0]], -1))
    rows = {"k": li[:, None], "k_scale": lsc[:, None, :, 0],
            "feat": pack_features(lop_features(li))[:, None]}
    return q[..., :dn], q_rope, rows


def _mla_query(cfg, lp, q_nope, q_rope):
    """The absorbed query q̃_h = (q_nope_h·W_UK_hᵀ) ‖ q_rope_h, int8 per
    head: → (qi [B, H, S, dl], qsc f32 [B, H, S]). W_UK is the k part of
    kv_b, one BitLinear per head (heads on the expert grid axis)."""
    b, s, h, dn = q_nope.shape
    x = q_nope.transpose(2, 0, 1, 3).reshape(h, b * s, dn)
    qc = qlinear_expert(lp["wuk"], x).reshape(h, b, s, -1)
    qt = jnp.concatenate([qc.transpose(1, 0, 2, 3),
                          q_rope.transpose(0, 2, 1, 3)], -1)
    qi, qsc = _q(qt)
    return qi, qsc[..., 0]


def _mla_values(cfg, lp, o):
    """Attention-weighted latent rows o [B, H, S, dc] → the heads' values
    o_h·W_UV_h (one BitLinear per head), joined → [B, S, H·dv]; o_proj
    follows outside the ``mla_attention`` mark."""
    b, h, s, dc = o.shape
    v = qlinear_expert(lp["wuv"], o.transpose(1, 0, 2, 3).reshape(h, b * s,
                                                                 dc))
    return v.reshape(h, b, s, -1).transpose(1, 2, 0, 3).reshape(b, s, -1)


def mla_prefill_chunk(cfg, lp, h, cl, *, start, kv_len):
    """:func:`attn_prefill_chunk` of latent attention: the chunk's latent
    rows are written at [start, start+C) and its absorbed queries attend
    causally over [0, kv_len) through the prefill dispatch, the latent
    rows as keys and as values (of which the c part is kept)."""
    c = h.shape[1]
    q_nope, q_rope, rows = _mla_latent(cfg, lp, h,
                                       start + jnp.arange(c)[None, :])
    cl = _write_chunk(cl, rows, start)
    with scope("mla_attention"):
        qi, qsc = _mla_query(cfg, lp, q_nope, q_rope)
        o = ops.prefill_attention(
            qi, qsc, cl["k"], cl["k"], cl["k_scale"], cl["k_scale"], kv_len,
            q_offset=start, causal=True,
            softmax_scale=mla_softmax_scale(cfg))
        v = _mla_values(cfg, lp, o[..., :cfg.kv_lora_rank])
    return qlinear(lp["wo"], v), cl


def mla_decode(cfg, lp, h, cl, lengths, *, use_lop, active, k_keep):
    """:func:`attn_decode` of latent attention: append each lane's latent
    row, then one fused decode-attention dispatch of the absorbed
    queries — 16 heads on one latent lane, the values the rows' first
    ``kv_lora_rank`` columns."""
    q_nope, q_rope, rows = _mla_latent(cfg, lp, h, lengths[:, None])
    cl = _write_token(cl, {k: v[:, :, 0] for k, v in rows.items()},
                      lengths, active)
    new_len = lengths + 1
    if active is not None:
        new_len = jnp.where(active, new_len, 0)
    with scope("mla_attention"):
        qi, qsc = _mla_query(cfg, lp, q_nope, q_rope)
        out = lop_decode_attention(cfg, qi[:, :, 0], qsc[:, :, :1], cl,
                                   new_len, window=cfg.swa_window,
                                   use_lop=use_lop and cfg.use_lop,
                                   k_keep=k_keep)
        if active is not None:
            out = jnp.where(active[:, None, None], out, 0.0)
        v = _mla_values(cfg, lp, out[:, :, None])
    return qlinear(lp["wo"], v), cl


# ===========================================================================
# Layer bodies
# ===========================================================================

def _mlp(cfg, lp, x):
    h = _norm(lp["ln2"], x, cfg.norm)
    if "moe" in lp:
        y, _ = moe_apply(cfg, lp["moe"], h)
    else:
        y = ffn_apply(cfg, lp["ffn"], h)
    return x + y


def _decoder_layer_prefill(cfg, lp, x, *, capacity, enc=None, cross_cap=None,
                           cross_len=None):
    x = _shard_batch(x)
    h = _norm(lp["ln1"], x, cfg.norm)
    attn_out, cache_l = attn_prefill(cfg, lp["attn"], h, capacity=capacity)
    x = x + attn_out
    out = {"self": cache_l}
    if enc is not None:
        cross_cache = build_cross_cache(cfg, lp["xattn"], enc, cross_cap)
        h = _norm(lp["ln_x"], x, cfg.norm)
        x = x + cross_attn_prefill(
            cfg, lp["xattn"], h, cross_cache, cross_len,
            kv_max=min(cross_cap, round_up(enc.shape[1], cfg.lop_block)))
        out["cross"] = cross_cache
    return _mlp(cfg, lp, x), out


def _decoder_layer_decode(cfg, lp, x, cl, lengths, *, use_lop, sp_axes,
                          cross_cl=None, cross_len=None, active=None,
                          k_keep=None):
    x = _shard_batch(x)
    h = _norm(lp["ln1"], x, cfg.norm)
    attn_out, new_cl = attn_decode(cfg, lp["attn"], h, cl, lengths,
                                   use_lop=use_lop, sp_axes=sp_axes,
                                   active=active, k_keep=k_keep)
    x = x + attn_out
    if cross_cl is not None:
        h = _norm(lp["ln_x"], x, cfg.norm)
        x = x + cross_attn_decode(cfg, lp["xattn"], h, cross_cl, cross_len,
                                  use_lop=use_lop, sp_axes=sp_axes)
    return _mlp(cfg, lp, x), new_cl


def _mamba_layer_prefill(cfg, lp, x):
    x = _shard_batch(x)
    h = _norm(lp["ln1"], x, cfg.norm)
    y, state = mamba_forward(cfg, lp["mamba"], h)
    return _mlp(cfg, lp, x + y), state


def _mamba_layer_decode(cfg, lp, x, state):
    x = _shard_batch(x)
    h = _norm(lp["ln1"], x, cfg.norm)
    y, state = mamba_decode_step(cfg, lp["mamba"], h, state)
    return _mlp(cfg, lp, x + y), state


def _rwkv_layer(cfg, lp, x, st):
    """Works for both prefill (T=S, zero states in st) and decode (T=1)."""
    x = _shard_batch(x)
    h = _norm(lp["ln1"], x, cfg.norm)
    y, x_tm, wkv = rwkv6.rwkv_time_mix(cfg, lp["tm"], h, st["x_tm"],
                                       st["wkv"])
    x = x + y
    h = _norm(lp["ln2"], x, cfg.norm)
    y, x_cm = rwkv6.rwkv_channel_mix(cfg, lp["tm"], h, st["x_cm"])
    return x + y, {"wkv": wkv, "x_tm": x_tm, "x_cm": x_cm}


# ===========================================================================
# Drivers
# ===========================================================================

def _embed(cfg, qp, tokens, patches=None):
    x = embedding_apply(qp["embed"], tokens)
    if cfg.family == "vlm" and patches is not None:
        proj = patches.astype(x.dtype) @ qp["projector"]["w"]
        x = jnp.concatenate([proj, x], axis=1)
    return x


def _logits(cfg, qp, x_last):
    """Final norm + f32 LM head, the head on fixed row tiles
    (:func:`repro.kernels.ops.map_row_tiles`): a pool lane's greedy token
    must equal the batch-1 lockstep path's (and the speculative verify
    rows'), bitwise, whatever the number of rows in the call."""
    x = _norm(qp["ln_f"], x_last, cfg.norm)
    return ops.map_row_tiles(lambda r: head_apply(qp["head"], r), x)


def prefill(cfg, qp, tokens, *, frames=None, patches=None, max_len=None,
            use_lop=True, sp_axes=None, cache_align=None, true_len=None):
    """Full-sequence forward writing the cache. → (last logits [B,V], cache).

    ``max_len`` sizes the cache capacity (defaults to the prompt length +
    one decode block of slack); ``cache_align`` aligns capacity for SP
    sharding (must match ``init_cache``'s align).

    ``true_len`` (scalar, may be traced) supports length-bucketed prefill
    compilation: ``tokens`` is right-padded to a bucket length and
    ``true_len`` marks the real sequence end — the cache length is set to
    it and the returned logits come from position ``true_len - 1``. Exact
    for causal-attention families (pad tokens can never attend backward
    into the answer row); recurrent families (hybrid/ssm) must pass
    unpadded prompts since their state integrates every position.
    """
    cfg = resolve_decode_flags(cfg)
    b = tokens.shape[0]
    x = _embed(cfg, qp, tokens, patches)
    s_total = x.shape[1]
    max_len = max(max_len if max_len is not None else 0, s_total)
    cap = round_up(max_len + 1, cache_align or cfg.lop_block)
    if true_len is None:
        true_len = s_total

    if cfg.family in ("dense", "moe", "vlm"):
        def body(x, lp):
            x, out = _decoder_layer_prefill(cfg, lp, x, capacity=cap)
            return x, out["self"]

        cache = {"lengths": jnp.full((b,), true_len, jnp.int32)}
        for name in _stacks(qp):
            x, cache[name] = _layer_scan(body, x, qp[name])
    elif cfg.family == "hybrid":
        def body(x, bp):
            outs_m = []
            attn_cache = None
            for j in range(cfg.attn_every):
                sub = bp[f"sub{j}"]
                if cfg.is_attn_layer(j):
                    x, out = _decoder_layer_prefill(cfg, sub, x, capacity=cap)
                    attn_cache = out["self"]
                else:
                    x, st = _mamba_layer_prefill(cfg, sub, x)
                    outs_m.append(st)
            stacked = jax.tree.map(lambda *a: jnp.stack(a), *outs_m)
            return x, {"attn": attn_cache, "mamba": stacked}

        x, blocks = _layer_scan(body, x, qp["blocks"])
        cache = {"lengths": jnp.full((b,), true_len, jnp.int32),
                 "blocks": blocks}
    elif cfg.family == "ssm":
        zeros = {
            "wkv": jnp.zeros((b, cfg.n_heads, cfg.hd, cfg.hd), jnp.float32),
            "x_tm": jnp.zeros((b, 1, cfg.d_model), jnp.float32),
            "x_cm": jnp.zeros((b, 1, cfg.d_model), jnp.float32),
        }

        def body(x, lp):
            x, st = _rwkv_layer(cfg, lp, x, zeros)
            return x, st

        x, layers_cache = _layer_scan(body, x, qp["layers"])
        cache = {"lengths": jnp.full((b,), true_len, jnp.int32),
                 "layers": layers_cache}
    elif cfg.family == "encdec":
        assert frames is not None
        enc = frames.astype(jnp.float32)
        enc_cap = round_up(max(cfg.cross_ctx, enc.shape[1]),
                           cache_align or cfg.lop_block)

        def enc_body(e, lp):
            e = _shard_batch(e)
            h = _norm(lp["ln1"], e, cfg.norm)
            q, k, v = _project_qkv(cfg, lp["attn"], h)
            qi, qsc = _q(q)
            ki, vi, ksc, vsc, _ = _quantize_kv(k, v)
            o = ops.prefill_attention(
                qi.transpose(0, 2, 1, 3), qsc[..., 0].transpose(0, 2, 1),
                ki, vi, ksc, vsc,
                jnp.full((e.shape[0],), e.shape[1], jnp.int32),
                causal=False, int8_logits=bool(cfg.int8_logits))
            o = o.transpose(0, 2, 1, 3).reshape(e.shape[0], e.shape[1],
                                                cfg.q_dim)
            e = e + qlinear(lp["attn"]["wo"], o)
            return _mlp(cfg, lp, e), None

        enc, _ = _layer_scan(enc_body, enc, qp["enc_layers"])
        enc = _norm(qp["ln_enc"], enc, cfg.norm)
        cross_len = jnp.full((b,), enc.shape[1], jnp.int32)

        def body(x, lp):
            x, out = _decoder_layer_prefill(cfg, lp, x, capacity=cap,
                                            enc=enc, cross_cap=enc_cap,
                                            cross_len=cross_len)
            return x, out

        x, outs = _layer_scan(body, x, qp["layers"])
        cache = {"lengths": jnp.full((b,), true_len, jnp.int32),
                 "layers": outs["self"], "cross": outs["cross"],
                 "cross_len": cross_len}
    else:
        raise ValueError(cfg.family)

    x_last = jax.lax.dynamic_index_in_dim(x, true_len - 1, axis=1,
                                          keepdims=False)
    logits = _logits(cfg, qp, x_last)
    return logits, cache


def prefill_chunk(cfg, qp, tokens, cache, *, start, seq_end, patches=None,
                  all_logits=False):
    """One fixed-shape chunk of chunked prefill. → (logits [B,V], cache).

    With ``all_logits=True`` the returned logits are [B, C, V] — one row
    per chunk position — instead of the single ``seq_end - 1`` row. This
    is the speculative-decoding verify call (DESIGN.md
    §Speculative-decoding): the chunk carries [t_last, d_1..d_γ], every
    row is scored exactly through the same fused prefill dispatch that
    decode is bitwise-pinned against, and row i is the target
    distribution for the token after position start+i.

    tokens [B, C] cover global stream positions [start, start+C) (for vlm
    the stream is [image prefix ‖ text] and the first chunk additionally
    carries ``patches``, so its embedded length is n_img + C at
    ``start = 0``). ``cache`` holds every earlier chunk's K/V at
    [0, start); this call writes positions [start, start+C) per layer and
    sets ``lengths = seq_end`` — the true end of the written prompt so
    far, which trails start+C only on a right-padded final chunk. The
    returned logits come from stream position ``seq_end - 1`` and are
    meaningful on the final chunk only (they seed the first decode
    token). ``start`` and ``seq_end`` may be traced, so ONE compile
    serves every chunk index of every prompt at this chunk shape.

    ``start`` need not begin at 0 for a lane's FIRST call: a prefix-cache
    hit (:class:`repro.serving.api.ExistingPrefix`) clones interned pages
    covering [0, start) and resumes here — bitwise the same carry as any
    later chunk, so a hit decodes token-identically to a cold prefill
    (DESIGN.md §Prefix-caching).

    Supported for the causal-attention families whose per-token compute
    is independent of how the prompt is split (dense, vlm, and — router
    caveats aside, DESIGN.md §Chunked-prefill — moe). Recurrent families
    (hybrid/ssm) integrate state over every position and encdec couples
    the compile to the encoder frames; they keep whole-prompt prefill.
    """
    cfg = resolve_decode_flags(cfg)
    if cfg.family not in ("dense", "moe", "vlm"):
        raise ValueError(f"chunked prefill is undefined for family "
                         f"{cfg.family!r} (needs causal attention with "
                         f"split-invariant per-token compute)")
    b = tokens.shape[0]
    x = _embed(cfg, qp, tokens, patches)
    c_total = x.shape[1]
    kv_len = jnp.full((b,), start + c_total, jnp.int32)

    def body(x, inp):
        lp, cl = inp
        x = _shard_batch(x)
        h = _norm(lp["ln1"], x, cfg.norm)
        attn_out, ncl = attn_prefill_chunk(cfg, lp["attn"], h, cl,
                                           start=start, kv_len=kv_len)
        return _mlp(cfg, lp, x + attn_out), ncl

    new_cache = dict(cache)
    for name in _stacks(qp):
        x, new_cache[name] = _layer_scan(body, x, (qp[name], cache[name]))
    new_cache["lengths"] = jnp.full((b,), seq_end, jnp.int32)
    if all_logits:
        return _logits(cfg, qp, x), new_cache
    idx = jnp.clip(seq_end - 1 - start, 0, c_total - 1)
    x_last = jax.lax.dynamic_index_in_dim(x, idx, axis=1, keepdims=False)
    logits = _logits(cfg, qp, x_last)
    return logits, new_cache


def serve_step(cfg, qp, cache, tokens, *, use_lop=True, sp_axes=None):
    """One decode step. tokens [B, 1] → (logits [B, V], updated cache).

    A slot-paged pool (``"active"`` in the cache) decodes only live lanes:
    inactive lanes write nothing, keep their ``lengths``, and their logits
    are meaningless (the scheduler never reads them).
    """
    cfg = resolve_decode_flags(cfg)
    lengths = cache["lengths"]
    active = cache.get("active")
    x = _embed(cfg, qp, tokens)
    new_cache = dict(cache)

    if cfg.family in ("dense", "moe", "vlm"):
        def body(x, inp):
            lp, cl = inp
            x, ncl = _decoder_layer_decode(cfg, lp, x, cl, lengths,
                                           use_lop=use_lop, sp_axes=sp_axes,
                                           active=active)
            return x, ncl

        for name in _stacks(qp):
            x, new_cache[name] = _layer_scan(body, x, (qp[name],
                                                       cache[name]))
    elif cfg.family == "hybrid":
        def body(x, inp):
            bp, bc = inp
            new_m = []
            mi = 0
            attn_cache = None
            for j in range(cfg.attn_every):
                sub = bp[f"sub{j}"]
                if cfg.is_attn_layer(j):
                    x, attn_cache = _decoder_layer_decode(
                        cfg, sub, x, bc["attn"], lengths, use_lop=use_lop,
                        sp_axes=sp_axes, active=active)
                else:
                    st = jax.tree.map(lambda a: a[mi], bc["mamba"])
                    x, st = _mamba_layer_decode(cfg, sub, x, st)
                    new_m.append(st)
                    mi += 1
            stacked = jax.tree.map(lambda *a: jnp.stack(a), *new_m)
            return x, {"attn": attn_cache, "mamba": stacked}

        x, blocks = _layer_scan(body, x, (qp["blocks"], cache["blocks"]))
        new_cache["blocks"] = blocks
    elif cfg.family == "ssm":
        def body(x, inp):
            lp, st = inp
            x, st = _rwkv_layer(cfg, lp, x, st)
            return x, st

        x, layers_cache = _layer_scan(body, x, (qp["layers"],
                                              cache["layers"]))
        new_cache["layers"] = layers_cache
    elif cfg.family == "encdec":
        def body(x, inp):
            lp, cl, xcl = inp
            x, ncl = _decoder_layer_decode(
                cfg, lp, x, cl, lengths, use_lop=use_lop, sp_axes=sp_axes,
                cross_cl=xcl, cross_len=cache["cross_len"], active=active)
            return x, ncl

        x, layers_cache = _layer_scan(
            body, x, (qp["layers"], cache["layers"], cache["cross"]))
        new_cache["layers"] = layers_cache
    else:
        raise ValueError(cfg.family)

    new_cache["lengths"] = lengths + (1 if active is None
                                      else active.astype(jnp.int32))
    logits = _logits(cfg, qp, x[:, -1])
    return logits, new_cache


def guard_logits(logits, fault_add=None):
    """Fault-injection + detection point of the decode hot path
    (DESIGN.md §Fault-tolerance). Adds a per-lane offset to the logits
    (zeros in production; NaN/inf rows when a
    :mod:`repro.serving.faults` plan is injecting) and computes the
    per-lane finiteness mask in-graph — one cheap reduction, no [B, V]
    host transfer. → (logits [B, V], ok [B] bool). A lane with
    ``ok=False`` must not have its sampled token emitted: the sample of
    a non-finite row is garbage; the scheduler quarantines the lane,
    rewinds its cache append bitwise (``rollback_slot``) and retries
    through the engine's no-LOP recovery step.
    """
    if fault_add is not None:
        logits = logits + fault_add[:, None]
    ok = jnp.all(jnp.isfinite(logits), axis=-1)
    return logits, ok


def draft_step(cfg, qp, cache, tokens, *, draft_layers: int,
               draft_k: int | None = None, use_lop=True):
    """One degraded-cost speculative DRAFT step. tokens [B, 1] →
    (logits [B, V], updated cache).

    The self-speculative predictor (DESIGN.md §Speculative-decoding):
    runs only the first ``draft_layers`` decoder layers — same weights,
    same per-layer cache lanes, same ``_decoder_layer_decode`` body as
    :func:`serve_step` — with the LOP selection budget optionally pinched
    to ``draft_k`` kept blocks, then projects through the SHARED logits
    head. No separate draft model: the truncated stack + sparser screen
    IS the cheap model.

    Cache discipline mirrors ``serve_step``: the drafted token's K/V/
    scale/LOP-feature rows are appended at position ``lengths`` for the
    first ``draft_layers`` layers only and ``lengths`` advances per
    active lane — provisional state that the verify call
    (:func:`prefill_chunk` with ``all_logits=True``) OVERWRITES for every
    layer at those same positions, and
    :func:`repro.serving.cache.rollback_slot` rewinds for rejected
    tokens. Between draft and verify the cache is transiently
    inconsistent (layers ≥ draft_layers hold zeros at the drafted
    positions); the scheduler never reads it in that window.

    Dense/vlm only — the families that declare ``supports_speculative``
    (a truncated scan needs a uniform causal layer stack, and the verify
    side needs chunked prefill).
    """
    cfg = resolve_decode_flags(cfg)
    if cfg.family not in ("dense", "vlm"):
        raise ValueError(f"speculative draft is undefined for family "
                         f"{cfg.family!r} (needs a uniform causal layer "
                         f"stack and a chunked-prefill verify path)")
    lengths = cache["lengths"]
    active = cache.get("active")
    x = _embed(cfg, qp, tokens)
    new_cache = dict(cache)
    full_layers = cache["layers"]
    head_qp = jax.tree.map(lambda a: a[:draft_layers], qp["layers"])
    head_cl = jax.tree.map(lambda a: a[:draft_layers], full_layers)

    def body(x, inp):
        lp, cl = inp
        x, ncl = _decoder_layer_decode(cfg, lp, x, cl, lengths,
                                       use_lop=use_lop, sp_axes=None,
                                       active=active, k_keep=draft_k)
        return x, ncl

    x, upd = _layer_scan(body, x, (head_qp, head_cl))
    new_cache["layers"] = jax.tree.map(
        lambda u, f: jnp.concatenate([u, f[draft_layers:]], axis=0),
        upd, full_layers)
    new_cache["lengths"] = lengths + (1 if active is None
                                      else active.astype(jnp.int32))
    logits = _logits(cfg, qp, x[:, -1])
    return logits, new_cache
