"""Model assembly: decoder stacks, Jamba superblocks, RWKV stacks, enc-dec,
VLM prefix — all under one ``init_params`` / ``forward_train`` API.

Stacks are ``lax.scan`` over layer-stacked params (compile-time compact for
the 80-cell dry-run) with per-layer remat (training memory discipline).
Heterogeneous Jamba layers scan over *superblocks* of ``attn_every`` layers
whose internal pattern (1 attention + 7 Mamba, MoE on odd positions) repeats
exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.distributed.partitioning import shard
from repro.models import rwkv6
from repro.models.attention import attention_apply, attention_init
from repro.models.layers import (embedding_apply, embedding_init, head_apply,
                                 linear_init, norm_apply, norm_init)
from repro.models.mamba import mamba_forward, mamba_init
from repro.models.moe import ffn_apply, ffn_init, moe_apply, moe_init
from repro.models.scan_utils import stacked_init


# ---------------------------------------------------------------------------
# Layer init/apply (homogeneous decoder / encoder layers)
# ---------------------------------------------------------------------------

def decoder_layer_init(key, cfg, *, moe: bool, cross: bool = False):
    keys = jax.random.split(key, 3)
    p, s = {}, {}
    p["ln1"], s["ln1"] = norm_init(cfg.d_model, cfg.norm)
    p["attn"], s["attn"] = attention_init(keys[0], cfg)
    if cross:
        p["ln_x"], s["ln_x"] = norm_init(cfg.d_model, cfg.norm)
        p["xattn"], s["xattn"] = attention_init(keys[1], cfg)
    p["ln2"], s["ln2"] = norm_init(cfg.d_model, cfg.norm)
    if moe:
        p["moe"], s["moe"] = moe_init(keys[2], cfg)
    else:
        p["ffn"], s["ffn"] = ffn_init(keys[2], cfg)
    return p, s


def decoder_layer_apply(cfg, p, x, *, positions, causal=True, cross_kv=None,
                        chunk_ctx=None):
    """One decoder layer. ``chunk_ctx`` is the float-path chunk-carry
    (DESIGN.md §Chunked-prefill): when ``x`` is the suffix chunk of a
    longer stream, pass the full pre-layer stream (prefix ‖ chunk) — the
    layer norms it through the same ln1 and lets the chunk's queries
    attend the whole context at their global offset, so the output equals
    the same rows of a full-stream call (no KV cache needed in the
    training/eval path)."""
    x = shard(x, "dp", None, None)
    h = norm_apply(p["ln1"], x, cfg.norm)
    if chunk_ctx is None:
        x = x + attention_apply(cfg, p["attn"], h, positions=positions,
                                causal=causal)
    else:
        hk = norm_apply(p["ln1"], chunk_ctx, cfg.norm)
        x = x + attention_apply(cfg, p["attn"], h, kv_x=hk, causal=causal,
                                positions=positions, chunk_carry=True,
                                q_offset=chunk_ctx.shape[1] - x.shape[1])
    if cross_kv is not None:
        h = norm_apply(p["ln_x"], x, cfg.norm)
        x = x + attention_apply(cfg, p["xattn"], h, kv_x=cross_kv,
                                use_rope=False)
    h = norm_apply(p["ln2"], x, cfg.norm)
    if "moe" in p:
        y, aux = moe_apply(cfg, p["moe"], h)
    else:
        y, aux = ffn_apply(cfg, p["ffn"], h), jnp.float32(0)
    return x + y, aux


# ---------------------------------------------------------------------------
# Jamba superblock
# ---------------------------------------------------------------------------

def superblock_init(key, cfg):
    n = cfg.attn_every
    keys = jax.random.split(key, n)
    p, s = {}, {}
    for j in range(n):
        moe = cfg.is_moe_layer(j)
        sub_p, sub_s = {}, {}
        sub_p["ln1"], sub_s["ln1"] = norm_init(cfg.d_model, cfg.norm)
        if cfg.is_attn_layer(j):
            sub_p["attn"], sub_s["attn"] = attention_init(keys[j], cfg)
        else:
            sub_p["mamba"], sub_s["mamba"] = mamba_init(keys[j], cfg)
        sub_p["ln2"], sub_s["ln2"] = norm_init(cfg.d_model, cfg.norm)
        kj = jax.random.fold_in(keys[j], 1)
        if moe:
            sub_p["moe"], sub_s["moe"] = moe_init(kj, cfg)
        else:
            sub_p["ffn"], sub_s["ffn"] = ffn_init(kj, cfg)
        p[f"sub{j}"], s[f"sub{j}"] = sub_p, sub_s
    return p, s


def superblock_apply(cfg, p, x, *, positions):
    aux_total = jnp.float32(0)
    for j in range(cfg.attn_every):
        sub = p[f"sub{j}"]
        x = shard(x, "dp", None, None)
        h = norm_apply(sub["ln1"], x, cfg.norm)
        if "attn" in sub:
            x = x + attention_apply(cfg, sub["attn"], h, positions=positions)
        else:
            y, _ = mamba_forward(cfg, sub["mamba"], h)
            x = x + y
        h = norm_apply(sub["ln2"], x, cfg.norm)
        if "moe" in sub:
            y, aux = moe_apply(cfg, sub["moe"], h)
            aux_total = aux_total + aux
        else:
            y = ffn_apply(cfg, sub["ffn"], h)
        x = x + y
    return x, aux_total


# ---------------------------------------------------------------------------
# RWKV layer
# ---------------------------------------------------------------------------

def rwkv_layer_init(key, cfg):
    p, s = {}, {}
    p["ln1"], s["ln1"] = norm_init(cfg.d_model, cfg.norm)
    p["tm"], s["tm"] = rwkv6.rwkv_init(key, cfg)
    p["ln2"], s["ln2"] = norm_init(cfg.d_model, cfg.norm)
    return p, s


def rwkv_layer_apply(cfg, p, x):
    b = x.shape[0]
    x = shard(x, "dp", None, None)
    zeros_prev = jnp.zeros((b, 1, cfg.d_model), x.dtype)
    state0 = jnp.zeros((b, cfg.n_heads, cfg.hd, cfg.hd), jnp.float32)
    h = norm_apply(p["ln1"], x, cfg.norm)
    y, _, _ = rwkv6.rwkv_time_mix(cfg, p["tm"], h, zeros_prev, state0)
    x = x + y
    h = norm_apply(p["ln2"], x, cfg.norm)
    y, _ = rwkv6.rwkv_channel_mix(cfg, p["tm"], h, zeros_prev)
    return x + y


# ---------------------------------------------------------------------------
# Full-model init
# ---------------------------------------------------------------------------

def init_unstacked(cfg, key):
    """(params, pspecs) of every leaf of ``init_params(cfg, key)`` that is
    not stacked over layers: embedding, head and final norms."""
    keys = jax.random.split(key, 8)
    p, s = {}, {}
    p["embed"], s["embed"] = embedding_init(keys[0], cfg.vocab_padded,
                                            cfg.d_model)
    p["ln_f"], s["ln_f"] = norm_init(cfg.d_model, cfg.norm)
    p["head"], s["head"] = linear_init(keys[1], cfg.d_model, cfg.vocab_padded)
    if cfg.family == "vlm":
        p["projector"], s["projector"] = linear_init(
            keys[3], cfg.d_model, cfg.d_model, spec=("fsdp", "tp"))
    if cfg.family == "encdec":
        p["ln_enc"], s["ln_enc"] = norm_init(cfg.d_model, cfg.norm)
    return p, s


def stacked_groups(cfg, key):
    """The layer-stacked groups of ``init_params(cfg, key)``: name →
    (per-layer init, group key, layer count). ``stacked_init`` splits the
    group key into one key per layer."""
    keys = jax.random.split(key, 8)
    if cfg.family in ("dense", "moe", "vlm"):
        groups = {"layers": (functools.partial(
            decoder_layer_init, cfg=cfg, moe=cfg.family == "moe"),
            keys[2], cfg.n_layers - cfg.first_k_dense)}
        if cfg.first_k_dense:
            # leading dense layers: a stack of their own (DeepSeek)
            groups = {"dense_layers": (functools.partial(
                decoder_layer_init, cfg=cfg, moe=False), keys[4],
                cfg.first_k_dense), **groups}
        return groups
    if cfg.family == "hybrid":
        assert cfg.n_layers % cfg.attn_every == 0
        return {"blocks": (functools.partial(superblock_init, cfg=cfg),
                           keys[2], cfg.n_layers // cfg.attn_every)}
    if cfg.family == "ssm":
        return {"layers": (functools.partial(rwkv_layer_init, cfg=cfg),
                           keys[2], cfg.n_layers)}
    if cfg.family == "encdec":
        return {
            "enc_layers": (functools.partial(decoder_layer_init, cfg=cfg,
                                             moe=False),
                           keys[2], cfg.n_encoder_layers),
            "layers": (functools.partial(decoder_layer_init, cfg=cfg,
                                         moe=False, cross=True),
                       keys[3], cfg.n_layers)}
    raise ValueError(cfg.family)


def init_params(cfg, key):
    """Returns (params, pspecs) for any family."""
    p, s = init_unstacked(cfg, key)
    for name, (layer_init, gkey, n) in stacked_groups(cfg, key).items():
        p[name], s[name] = stacked_init(layer_init, gkey, n)
    return p, s


# ---------------------------------------------------------------------------
# Forward (training path: QAT BitLinear everywhere, f32 reductions)
# ---------------------------------------------------------------------------

def _scan_stack(body, x, stacked, *, remat: bool = True):
    """Scan ``body(x, layer_params) → (x, aux)`` over layer-stacked params."""
    from repro.models.scan_utils import accounting_unroll

    def step(carry, lp):
        x, aux = carry
        x, a = body(x, lp)
        return (x, aux + a), None

    step_fn = jax.checkpoint(step) if remat else step
    length = jax.tree.leaves(stacked)[0].shape[0]
    (x, aux), _ = jax.lax.scan(step_fn, (x, jnp.float32(0)), stacked,
                               unroll=accounting_unroll(length))
    return x, aux


def forward_train(cfg, params, tokens, *, frames=None, patches=None,
                  remat: bool = True):
    """→ (logits [B, T_text, vocab_padded], moe_aux).

    tokens [B, T]; frames [B, S_audio, D] (encdec stub frontend);
    patches [B, n_img, D] (vlm stub vision tower).

    The residual stream runs in ``cfg.act_dtype`` (bf16 in production);
    norms/softmax/loss reductions stay f32 per the absmax-barrier
    discipline; master params are f32 and cast at use.
    """
    act_dtype = jnp.dtype(cfg.act_dtype)
    x = embedding_apply(params["embed"], tokens).astype(act_dtype)
    b, t = tokens.shape
    positions = jnp.arange(t)[None, :]
    aux = jnp.float32(0)

    if cfg.family in ("dense", "moe"):
        body = lambda x, lp: decoder_layer_apply(cfg, lp, x,
                                                 positions=positions)
        x, aux = _scan_stack(body, x, params["layers"], remat=remat)
    elif cfg.family == "vlm":
        assert patches is not None
        proj = patches.astype(x.dtype) @ params["projector"]["w"].astype(
            x.dtype)
        x = jnp.concatenate([proj, x], axis=1)
        positions = jnp.arange(x.shape[1])[None, :]
        body = lambda x, lp: decoder_layer_apply(cfg, lp, x,
                                                 positions=positions)
        x, aux = _scan_stack(body, x, params["layers"], remat=remat)
        x = x[:, patches.shape[1]:]                     # text positions only
    elif cfg.family == "hybrid":
        body = lambda x, bp: superblock_apply(cfg, bp, x, positions=positions)
        x, aux = _scan_stack(body, x, params["blocks"], remat=remat)
    elif cfg.family == "ssm":
        body = lambda x, lp: (rwkv_layer_apply(cfg, lp, x), jnp.float32(0))
        x, aux = _scan_stack(body, x, params["layers"], remat=remat)
    elif cfg.family == "encdec":
        assert frames is not None
        enc = frames.astype(x.dtype)
        enc_pos = jnp.arange(enc.shape[1])[None, :]
        enc_body = lambda e, lp: decoder_layer_apply(
            cfg, lp, e, positions=enc_pos, causal=False)
        enc, _ = _scan_stack(enc_body, enc, params["enc_layers"], remat=remat)
        enc = norm_apply(params["ln_enc"], enc, cfg.norm)
        body = lambda x, lp: decoder_layer_apply(cfg, lp, x,
                                                 positions=positions,
                                                 cross_kv=enc)
        x, aux = _scan_stack(body, x, params["layers"], remat=remat)
    else:
        raise ValueError(cfg.family)

    x = norm_apply(params["ln_f"], x, cfg.norm)
    logits = head_apply(params["head"], x)
    logits = shard(logits, "dp", None, "tp")
    return logits, aux
