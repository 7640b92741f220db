"""Training/prefill attention (bf16/f32 path) with head-level streaming.

The exact-attention compute here is the *chunked* (flash-style) schedule:
queries stream in chunks while the f32 softmax reductions stay fused with the
logit tiles — the jnp expression of the paper's "reductions overlap with
linear tiles". The serving path (int8 + LOP screen) lives in
:mod:`repro.serving.engine` and the Pallas kernels.

Sharding: heads go to the ``model`` axis when divisible; otherwise the query
*sequence* is sharded (SP) — this keeps every assigned arch (12-head whisper,
40-head qwen32b, 56-head llava) legal on a 16-way model axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.partitioning import current_mesh, shard
from repro.models.layers import linear_apply, linear_init, rope

NEG_INF = -1e30


def _model_axis_size() -> int:
    mesh = current_mesh()
    if mesh is None:
        return 1
    return mesh.shape.get("model", 1)


def shard_heads_or_seq(x: jax.Array, n_heads: int) -> jax.Array:
    """x [B, S, H, dh] → head-sharded when H divides the model axis.

    Non-divisible head counts are left for the chunk-row SP sharding inside
    :func:`chunked_attention` (constraining S here would make the chunk
    scan slice a sharded axis — involuntary resharding per step).
    """
    m = _model_axis_size()
    if n_heads % m == 0:
        return shard(x, "dp", None, "tp", None)
    return x


def chunked_attention(q, k, v, *, causal: bool, window: int = 0,
                      q_offset: int = 0, chunk: int = 512,
                      softmax_scale: float | None = None) -> jax.Array:
    """Chunked exact attention with GQA.

    q [B, Sq, H, dh]; k/v [B, Skv, Hkv, dh] (H % Hkv == 0) → [B, Sq, H, dh].
    ``window > 0`` applies a sliding-window (SWA) causal mask.
    ``q_offset`` is the absolute position of q[0] (prefill continuation).

    GQA keys/values are repeated to the flat H dim so the head axis stays
    shardable end-to-end (a (Hkv, G) split would break TP head sharding —
    SPMD falls back to full replication). When H doesn't divide the model
    axis, the chunk's query rows are SP-sharded instead.
    """
    import os
    b, sq, h, dh = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    if softmax_scale is None:
        softmax_scale = dh ** -0.5
    # accounting probes raise the chunk so unrolling stays tractable —
    # tiling is flop/byte-invariant, so the differential stays exact
    chunk = int(os.environ.get("REPRO_ATTN_CHUNK", chunk))
    if hkv != h:
        k = jnp.repeat(k, h // hkv, axis=2)
        v = jnp.repeat(v, h // hkv, axis=2)
    head_sharded = h % _model_axis_size() == 0
    if head_sharded:
        k = shard(k, "dp", None, "tp", None)
        v = shard(v, "dp", None, "tp", None)

    chunk = min(chunk, sq)
    pad = (-sq) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = q.shape[1] // chunk
    qc = q.reshape(b, nc, chunk, h, dh).transpose(1, 0, 2, 3, 4)

    kpos = jnp.arange(skv)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    def body(_, args):
        qi, ci = args                                    # [B, C, H, dh]
        if head_sharded:
            qi = shard(qi, "dp", None, "tp", None)
        else:
            qi = shard(qi, "dp", "sp", None, None)
        logits = jnp.einsum("bqhd,bkhd->bhqk", qi.astype(jnp.float32),
                            kf) * softmax_scale
        qpos = q_offset + ci * chunk + jnp.arange(chunk)
        mask = jnp.ones((chunk, skv), bool)
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        if window:
            mask &= (qpos[:, None] - kpos[None, :]) < window
        logits = jnp.where(mask[None, None], logits, NEG_INF)
        p = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, vf)
        return None, o.astype(q.dtype)

    from repro.models.scan_utils import accounting_unroll
    _, oc = jax.lax.scan(body, None, (qc, jnp.arange(nc)),
                         unroll=accounting_unroll(nc))
    o = oc.transpose(1, 0, 2, 3, 4).reshape(b, nc * chunk, h, dv)
    return o[:, :sq]


# ---------------------------------------------------------------------------
# Full attention block (projections + rope + attention + output proj)
# ---------------------------------------------------------------------------

def attention_init(key, cfg):
    if cfg.mla:
        return mla_init(key, cfg)
    keys = jax.random.split(key, 4)
    d = cfg.d_model
    p, s = {}, {}
    p["wq"], s["wq"] = linear_init(keys[0], d, cfg.q_dim, bias=cfg.qkv_bias)
    p["wk"], s["wk"] = linear_init(keys[1], d, cfg.kv_dim, bias=cfg.qkv_bias)
    p["wv"], s["wv"] = linear_init(keys[2], d, cfg.kv_dim, bias=cfg.qkv_bias)
    p["wo"], s["wo"] = linear_init(keys[3], cfg.q_dim, d, spec=("tp", "fsdp"))
    return p, s


def attention_apply(cfg, p, x, *, kv_x=None, causal=True, positions=None,
                    use_rope=True, chunk_carry: bool = False,
                    q_offset: int = 0):
    """Self-attention (kv_x=None), cross-attention, or chunk-carry.

    x [B, S, D] → [B, S, D]. Projections are BitLinear under QAT.

    Chunk-carry (the float-path mirror of the engine's chunked prefill,
    DESIGN.md §Chunked-prefill) is an explicit opt-in: when ``x`` is a
    *suffix chunk* of a longer self-attention stream, pass the full
    stream (prefix ‖ chunk) as ``kv_x`` with ``chunk_carry=True`` and
    the chunk's absolute start as ``q_offset`` — keys rope at positions
    [0, S_kv), queries at [q_offset, q_offset + S), and the causal/SWA
    mask runs in global positions, so the chunk rows equal the same rows
    of one full-stream call. Without the flag, ``kv_x`` keeps its
    original invariant: plain cross-attention (non-causal, no rope,
    no window), whatever ``use_rope`` says.
    """
    if cfg.mla:
        raise NotImplementedError("latent attention runs on the served "
                                  "path only (serving/engine.py)")
    b, sq, _ = x.shape
    src = x if kv_x is None else kv_x
    skv = src.shape[1]
    self_like = kv_x is None or chunk_carry

    q = linear_apply(p["wq"], x, quant=cfg.quant)
    k = linear_apply(p["wk"], src, quant=cfg.quant)
    v = linear_apply(p["wv"], src, quant=cfg.quant)
    q = q.reshape(b, sq, cfg.n_heads, cfg.hd)
    k = k.reshape(b, skv, cfg.n_kv_heads, cfg.hd)
    v = v.reshape(b, skv, cfg.n_kv_heads, cfg.hd)

    if use_rope and self_like:
        if positions is None:
            positions = q_offset + jnp.arange(sq)[None, :]
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, jnp.arange(skv)[None, :] if chunk_carry else positions,
                 cfg.rope_theta)

    q = shard_heads_or_seq(q, cfg.n_heads)
    k = shard_heads_or_seq(k, cfg.n_kv_heads)
    v = shard_heads_or_seq(v, cfg.n_kv_heads)

    o = chunked_attention(q, k, v, causal=causal and self_like,
                          window=cfg.swa_window if self_like else 0,
                          q_offset=q_offset)
    o = o.reshape(b, sq, cfg.q_dim)
    return linear_apply(p["wo"], o, quant=cfg.quant)


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1)
# ---------------------------------------------------------------------------

def mla_init(key, cfg):
    """q_proj; kv_a (the latent c ‖ the shared k_rope) and its RMSNorm on
    c; kv_b (c → per head k_nope ‖ v); o_proj. No q-LoRA."""
    keys = jax.random.split(key, 4)
    d, h = cfg.d_model, cfg.n_heads
    p, s = {}, {}
    p["wq"], s["wq"] = linear_init(keys[0], d, cfg.mla_q_dim)
    p["wkv_a"], s["wkv_a"] = linear_init(keys[1], d, cfg.latent_dim)
    p["kv_norm"], s["kv_norm"] = ({"g": jnp.ones((cfg.kv_lora_rank,),
                                                 jnp.float32)},
                                  {"g": (None,)})
    p["wkv_b"], s["wkv_b"] = linear_init(
        keys[2], cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim
                                        + cfg.v_head_dim))
    p["wo"], s["wo"] = linear_init(keys[3], h * cfg.v_head_dim, d,
                                   spec=("tp", "fsdp"))
    return p, s


def mla_attend(q_nope, q_rope, c, k_rope, w_kb, *, softmax_scale: float,
               causal: bool = True):
    """The published (non-absorbed) latent attention core, float.

    q_nope [B, S, H, dn]; q_rope [B, S, H, dr] (rotated); c [B, S, dc]
    (normed latent); k_rope [B, S, dr] (rotated, shared by the heads);
    w_kb [dc, H·(dn+dv)] (kv_b, per head k_nope ‖ v) → [B, S, H, dv].
    Keys are k_nope = c·W_UK ‖ k_rope, values v = c·W_UV."""
    b, s, h, dn = q_nope.shape
    kv = (c @ w_kb).reshape(b, s, h, -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope[:, :, None], q_rope.shape)],
        -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    return chunked_attention(q, k, kv[..., dn:], causal=causal,
                             softmax_scale=softmax_scale)
