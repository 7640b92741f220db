"""FFN and token-choice top-k Mixture-of-Experts.

Training dispatch is capacity-bucketed einsum dispatch over token
*groups* (scanned), so the one-hot dispatch tensor stays ``[group, E,
capacity]`` — small and transient — instead of ``[tokens, E, capacity]``.
Expert weights are sharded TP-inside-expert (``[E, d, ff]`` with ff on
the model axis), which divides for every assigned expert count; a
shard_map all-to-all EP variant lives in
:mod:`repro.distributed.expert_parallel`.

The served path (packed expert weights) is *dropless* instead
(:func:`moe_serve`, DESIGN.md §Latent-attention-and-dropless-experts): every expert's
capacity is the call's row count, so no token is dropped and a token's
output depends on its own row alone — a prompt prefilled whole, in
chunks, or decoded beside any other lanes routes and computes the same.
Shared experts (DeepSeek) run as one always-on FFN beside the routed
ones.

All expert projections are BitLinear under the ternary flow (BitNet applies
to every weight projection — MoE experts included); the router stays f32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.qlinear import (ffn_node_apply, is_fused_ffn, is_packed,
                                qlinear_expert)
from repro.core.marks import scope
from repro.core.ternary import ste_ternary
from repro.distributed.partitioning import shard
from repro.models.layers import linear_apply, linear_init


# ---------------------------------------------------------------------------
# Dense FFN (gated silu, or plain gelu for whisper)
# ---------------------------------------------------------------------------

def ffn_init(key, cfg, d_ff: int | None = None):
    keys = jax.random.split(key, 3)
    d, f = cfg.d_model, d_ff or cfg.d_ff
    p, s = {}, {}
    if cfg.gated_ffn:
        p["w_gate"], s["w_gate"] = linear_init(keys[0], d, f)
        p["w_up"], s["w_up"] = linear_init(keys[1], d, f)
    else:
        p["w_up"], s["w_up"] = linear_init(keys[1], d, f)
    p["w_down"], s["w_down"] = linear_init(keys[2], f, d, spec=("tp", "fsdp"))
    return p, s


def ffn_apply(cfg, p, x):
    if is_fused_ffn(p):
        # serving format: the whole FFN (gate·up → in-VMEM absmax barrier
        # → down) is ONE fused dispatch — bitwise the unfused chain below
        return ffn_node_apply(p, x, gated=cfg.gated_ffn,
                              act="silu" if cfg.gated_ffn else "gelu")
    if cfg.gated_ffn:
        h = jax.nn.silu(linear_apply(p["w_gate"], x, quant=cfg.quant))
        h = h * linear_apply(p["w_up"], x, quant=cfg.quant)
    else:
        h = jax.nn.gelu(linear_apply(p["w_up"], x, quant=cfg.quant))
    h = shard(h, "dp", None, "tp")
    return linear_apply(p["w_down"], h, quant=cfg.quant)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def moe_init(key, cfg):
    keys = jax.random.split(key, 4)
    d, f, e = cfg.d_model, cfg.expert_width, cfg.n_experts
    scale = d ** -0.5
    p = {
        "router": jax.random.normal(keys[0], (d, e), jnp.float32) * scale,
        "w_gate": jax.random.normal(keys[1], (e, d, f), jnp.float32) * scale,
        "w_up": jax.random.normal(keys[2], (e, d, f), jnp.float32) * scale,
        "w_down": jax.random.normal(keys[3], (e, f, d), jnp.float32)
                  * (f ** -0.5),
    }
    s = {
        "router": (None, None),
        "w_gate": (None, "fsdp", "tp"),
        "w_up": (None, "fsdp", "tp"),
        "w_down": (None, "tp", "fsdp"),
    }
    if cfg.n_shared_experts:
        p["shared"], s["shared"] = ffn_init(
            jax.random.fold_in(key, 4), cfg,
            cfg.n_shared_experts * cfg.expert_width)
    return p, s


def _expert_linear(w, x, quant: str):
    """x [E, C, d_in] @ w [E, d_in, d_out].

    Serving format (packed dict) → integer-domain qlinear; training format
    (raw array) → plain einsum (STE fake-quant + dtype cast happen ONCE per
    layer in :func:`_prepare_expert_weights`, outside the group scan).
    """
    if is_packed(w):
        return qlinear_expert(w, x)
    # hillclimb flag: bf16 accumulation keeps the expert weight-grad
    # all-reduce in bf16 (halves the dominant collective of MoE training)
    import os
    pref = (None if os.environ.get("REPRO_BF16_EXPERT_ACC") == "1"
            else jnp.float32)
    return jnp.einsum("ecd,edf->ecf", x, w,
                      preferred_element_type=pref).astype(x.dtype)


def _prepare_expert_weights(cfg, p, act_dtype):
    """Hoist per-layer expert-weight work out of the group scan:

    QAT fake-quant (STE) once, cast to the activation dtype (so the FSDP
    all-gather moves bf16, not f32 master weights), and constrain to the
    gathered TP layout — the scan body then closes over loop-INVARIANT
    gathered weights instead of re-gathering every group step.
    """
    p = dict(p)
    for name in ("w_gate", "w_up", "w_down"):
        if name not in p or is_packed(p[name]):
            continue
        w = p[name]
        if cfg.quant == "ternary":
            w = ste_ternary(w.reshape(-1, w.shape[-1])).reshape(w.shape)
        w = w.astype(act_dtype)
        p[name] = shard(w, None, None, "tp")
    return p


def _expert_ffn(cfg, p, xe):
    """Every expert's FFN over its rows xe [E, C, d] → [E, C, d]."""
    if is_fused_ffn(p):
        # serving format: every expert's gate·up → barrier → down runs in
        # ONE grouped dispatch (expert = grid axis of the fused kernel)
        return ffn_node_apply(p, xe, gated=cfg.gated_ffn,
                              act="silu" if cfg.gated_ffn else "gelu")
    if cfg.gated_ffn:
        h = jax.nn.silu(_expert_linear(p["w_gate"], xe, cfg.quant))
        h = h * _expert_linear(p["w_up"], xe, cfg.quant)
    else:
        h = jax.nn.gelu(_expert_linear(p["w_up"], xe, cfg.quant))
    h = shard(h, None, None, "tp")
    return _expert_linear(p["w_down"], h, cfg.quant)


def _dispatch_group(cfg, p, x):
    """One token group [T, d] → MoE output [T, d] + aux losses."""
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    # capacity floor min(t, 8) keeps tiny decode groups drop-free
    cap = int(max(-(-t * k * cfg.capacity_factor // e), k, min(t, 8)))

    logits = x.astype(jnp.float32) @ p["router"]                  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)                 # [T, k]
    if cfg.norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert's capacity bucket
    choice_mask = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)    # [T, k, E]
    flat = choice_mask.reshape(t * k, e)
    pos = jnp.cumsum(flat, axis=0) - flat                         # pre-count
    pos = jnp.sum(pos.reshape(t, k, e) * choice_mask, -1)         # [T, k]
    keep = pos < cap                                              # overflow drop

    # dispatch one-hot [T, E, cap]
    disp = (jax.nn.one_hot(pos, cap, dtype=x.dtype)[:, :, None, :]
            * choice_mask[..., None].astype(x.dtype)
            * keep[..., None, None].astype(x.dtype))              # [T,k,E,cap]
    disp = jnp.sum(disp, axis=1)                                  # [T, E, cap]
    comb = disp * jnp.sum(
        gate_vals[:, :, None, None] * choice_mask[..., None].astype(x.dtype)
        * keep[..., None, None].astype(x.dtype), axis=1)          # weighted

    xe = jnp.einsum("tec,td->ecd", disp, x)                       # [E, cap, d]
    ye = _expert_ffn(cfg, p, xe)                                  # [E, cap, d]
    y = jnp.einsum("tec,ecd->td", comb, ye)

    # load-balancing aux loss (Switch-style)
    frac_tokens = jnp.mean(jnp.sum(choice_mask, 1).astype(jnp.float32), 0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens / k * frac_probs)
    return y.astype(x.dtype), aux


def moe_apply(cfg, p, x):
    """x [B, S, d] → ([B, S, d], aux_loss). Groups of ``moe_group`` tokens.

    The group scan must iterate an UNSHARDED axis (scanning a dp-sharded
    axis makes SPMD gather every slice). Tokens are regrouped so each scan
    step takes ``moe_group/dp`` tokens from EVERY data shard: the step's
    token dim stays dp-sharded, the step axis is replicated.
    """
    import math

    from repro.distributed.partitioning import current_mesh, dp_axes

    b, s, d = x.shape
    if is_fused_ffn(p) or is_packed(p["w_up"]):
        return moe_serve(cfg, p, x), jnp.float32(0)
    p = _prepare_expert_weights(cfg, p, x.dtype)
    flat = x.reshape(b * s, d)
    t = flat.shape[0]

    mesh = current_mesh()
    dp = (math.prod(int(mesh.shape[a]) for a in dp_axes(mesh))
          if mesh is not None else 1)
    if t % dp != 0:
        dp = 1                                     # tiny/odd batch: local
    t_loc = t // dp
    grp_loc = max(1, min(cfg.moe_group // dp, t_loc))
    pad_loc = (-t_loc) % grp_loc
    if pad_loc:
        flat = (flat.reshape(dp, t_loc, d) if dp > 1 else flat[None])
        flat = jnp.pad(flat, ((0, 0), (0, pad_loc), (0, 0)))
        flat = flat.reshape(dp * (t_loc + pad_loc), d)
        t_loc = t_loc + pad_loc
    steps = t_loc // grp_loc

    # [dp·T_loc, d] → [steps, dp, grp_loc, d]: the step axis is unsharded
    # (scannable), the dp axis is a *batched* dim — each data shard
    # dispatches its own grp_loc tokens with zero cross-shard traffic.
    groups = flat.reshape(dp, steps, grp_loc, d).transpose(1, 0, 2, 3)
    groups = shard(groups, None, "dp", None, None)

    def body(_, xg):                                   # xg [dp, grp_loc, d]
        y, aux = jax.vmap(lambda g: _dispatch_group(cfg, p, g))(xg)
        return None, (y, jnp.mean(aux))

    from repro.models.scan_utils import accounting_unroll
    _, (ys, auxs) = jax.lax.scan(body, None, groups,
                                 unroll=accounting_unroll(steps))
    y = ys.transpose(1, 0, 2, 3).reshape(dp, t_loc, d)
    y = y[:, : t // dp].reshape(t, d)
    y = y.reshape(b, s, d)
    return y, jnp.mean(auxs)


def moe_serve(cfg, p, x):
    """Dropless MoE over packed expert weights: x [B, S, d] → [B, S, d].

    Each of the T = B·S rows picks its top-k experts by the router's
    softmax (the router matmul at HIGHEST precision on fixed row tiles,
    so a row's routing never depends on the other rows in the call).
    Expert e's bucket holds T rows — top-k experts are distinct, so no
    expert can receive more and no token is dropped; a routed row is
    copied into its slot by a scatter and its output read back by a
    gather (exact moves, no one-hot product). Every expert's FFN runs
    over its bucket in one grouped dispatch, each row behind its own
    absmax barrier, and the k outputs combine weighted by the gates in a
    fixed order. Shared experts are one FFN over every row. This form
    computes E·T expert rows, the empty slots included.
    """
    from repro.kernels import ops

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    flat = x.reshape(b * s, d).astype(jnp.float32)
    t = flat.shape[0]
    with scope("moe_route"):
        router = p["router"].astype(jnp.float32)
        probs = ops.map_row_tiles(
            lambda r: jax.nn.softmax(jnp.dot(
                r, router, precision=jax.lax.Precision.HIGHEST), axis=-1),
            flat)                                             # [T, E]
        gate, idx = jax.lax.top_k(probs, k)                   # [T, k]
        if cfg.norm_topk_prob:
            total = gate[:, 0]
            for j in range(1, k):
                total = total + gate[:, j]
            gate = gate / jnp.maximum(total, 1e-9)[:, None]
        # slot of (row, choice) in its expert's bucket: rows in order
        hits = (idx[..., None] == jnp.arange(e)).astype(jnp.int32)
        before = jnp.cumsum(hits.reshape(t * k, e), axis=0).reshape(
            t, k, e) - hits
        pos = jnp.sum(before * hits, axis=-1)                 # [T, k]
        xe = jnp.zeros((e, t, d), jnp.float32).at[idx, pos].set(
            jnp.broadcast_to(flat[:, None], (t, k, d)), unique_indices=True)
    with scope("moe_experts"):
        ye = _expert_ffn(cfg, p, xe)                          # [E, T, d]
        out = ye[idx, pos]                                    # [T, k, d]
        y = gate[:, 0, None] * out[:, 0]
        for j in range(1, k):
            y = y + gate[:, j, None] * out[:, j]
        if "shared" in p:
            y = y + ffn_apply(cfg, p["shared"], flat)
    return y.reshape(b, s, d).astype(x.dtype)
