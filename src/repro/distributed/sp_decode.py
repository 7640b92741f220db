"""Sequence-parallel LOP decode attention (shard_map core).

The production decode path (DESIGN.md §5): the KV/feature cache's token axis
is sharded across the ``model`` axis (SP), because GQA kv-head counts (8, 12,
40…) don't divide a 16-way axis but block-aligned token shards always do.

Per M-shard, each rank:
  1. writes the new token if it owns position ``lengths[b]``,
  2. runs the SAME fused decode kernel as the local path
     (:func:`repro.kernels.ops.decode_attention`) over its local shard,
     passing ``pos_offset = rank · M_local`` so validity masking and the
     candidate live-intervals land on global token positions, and a local
     **quota** of ⌈K/nshards⌉ candidate blocks (beyond-paper adaptation:
     per-shard quotas keep selection collective-free and perfectly
     load-balanced — every rank gathers the same number of blocks, so no
     stragglers),
  3. merges the kernel's *unnormalized* softmax stats (m, ℓ, out·ℓ) across
     shards flash-decoding style (pmax + psum).

The screen → select → exact pipeline itself is not duplicated here — it
lives once, inside the fused kernel / its jnp oracle (DESIGN.md
§Fused-decode-kernel). Total candidates = nshards·⌈K/nshards⌉ ≈ K; recall
vs the paper's global top-K is validated in tests/test_distributed.py.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import resolve_decode_flags
from repro.core.qlinear import is_packed  # noqa: F401 (doc cross-ref)
from repro.distributed.partitioning import current_mesh, dp_axes
from repro.kernels import ops
from repro.serving.lop_select import k_keep_blocks


def _write_token_local(cl, ki, vi, ksc, vsc, feat, lengths, offset, m_loc,
                       active=None):
    """Masked per-rank cache append (only the owner shard writes; retired
    slot-pool lanes never write)."""
    local = lengths - offset                              # [B]
    ok = (local >= 0) & (local < m_loc)
    if active is not None:
        ok &= active
    pos = jnp.clip(local, 0, m_loc - 1)

    def wr(arr, val, p_, ok_):
        # arr [Hkv, M, d]; val [Hkv, d]
        upd = jax.lax.dynamic_update_slice(
            arr, val[:, None], (0, p_) + (0,) * (arr.ndim - 2))
        return jnp.where(ok_, upd, arr)

    def wr_scale(arr, val, p_, ok_):
        upd = jax.lax.dynamic_update_slice(arr, val[:, None], (0, p_))
        return jnp.where(ok_, upd, arr)

    cl = dict(cl)
    cl["k"] = jax.vmap(wr)(cl["k"], ki, pos, ok)
    cl["v"] = jax.vmap(wr)(cl["v"], vi, pos, ok)
    cl["feat"] = jax.vmap(wr)(cl["feat"], feat, pos, ok)
    cl["k_scale"] = jax.vmap(wr_scale)(cl["k_scale"], ksc[..., 0], pos, ok)
    cl["v_scale"] = jax.vmap(wr_scale)(cl["v_scale"], vsc[..., 0], pos, ok)
    return cl


def sp_decode_attention(cfg, qi, qsc, ki, vi, ksc, vsc, feat, cl, lengths, *,
                        window: int, use_lop: bool, sp_axes: tuple,
                        write: bool = True, active=None):
    """SP decode attention over an M-sharded cache layer.

    qi int8 [B, H, dh]; qsc [B, H, 1]; ki/vi int8 [B, Hkv, dh] (new token);
    cl cache layer (token axis sharded over ``sp_axes``); lengths [B];
    active [B] bool (slot-pool lanes; None = all live).
    → (out f32 [B, H, dh], new cache layer).
    """
    mesh = current_mesh()
    assert mesh is not None, "sp decode requires an active mesh"
    cfg = resolve_decode_flags(cfg)
    b, h, dh = qi.shape
    if active is None:
        active = jnp.ones((b,), jnp.bool_)
    m_global = cl["k"].shape[2]
    nshards = math.prod(int(mesh.shape[a]) for a in sp_axes)
    m_loc = m_global // nshards
    block = cfg.lop_block
    k_keep = max(1, -(-k_keep_blocks(cfg, m_global) // nshards))  # quota

    bdp = dp_axes(mesh)
    batch_ax = bdp if b % math.prod(int(mesh.shape[a]) for a in bdp) == 0 \
        else None
    # leftover dp axes that are not consumed by batch or M sharding stay
    # replicated inside the region
    cache_spec = {
        "k": P(batch_ax, None, sp_axes, None),
        "v": P(batch_ax, None, sp_axes, None),
        "k_scale": P(batch_ax, None, sp_axes),
        "v_scale": P(batch_ax, None, sp_axes),
        "feat": P(batch_ax, None, sp_axes, None),
    }
    rep2 = P(batch_ax, None, None)
    rep1 = P(batch_ax)

    def body(qi, qsc, ki, vi, ksc, vsc, feat_new, cl, lengths, act):
        # shard rank along the sp axes → global token offset of this shard
        ridx = jnp.int32(0)
        for a in sp_axes:
            ridx = ridx * mesh.shape[a] + jax.lax.axis_index(a)
        offset = ridx * m_loc
        if write:
            cl = _write_token_local(cl, ki, vi, ksc, vsc, feat_new, lengths,
                                    offset, m_loc, active=act)
        new_len = lengths + (1 if write else 0)
        # retired lanes see an empty cache (nothing valid to screen/select)
        new_len = jnp.where(act, new_len, 0)

        # the same fused kernel as the local path, shifted to this shard's
        # global positions; stats come back unnormalized for the merge
        out, m, l = ops.decode_attention(
            qi, qsc, cl["k"], cl["v"], cl["k_scale"], cl["v_scale"],
            cl["feat"], new_len, block=block, k_keep=k_keep, window=window,
            use_lop=use_lop, shared_select=bool(cfg.gqa_shared_select),
            pos_offset=offset, return_stats=True)

        # flash-decoding merge across M shards (out·ℓ recovers the raw
        # accumulator; empty shards carry m = −inf, ℓ = 0)
        m_g = jax.lax.pmax(m, sp_axes)
        w = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * w, sp_axes)
        acc_g = jax.lax.psum(out * (l * w), sp_axes)
        out = acc_g / jnp.maximum(l_g, 1e-20)
        return out, cl

    new_tok_spec2 = P(batch_ax, None, None)
    in_specs = (new_tok_spec2, new_tok_spec2, new_tok_spec2, new_tok_spec2,
                new_tok_spec2, new_tok_spec2,
                new_tok_spec2 if feat is not None else None,
                cache_spec, rep1, rep1)
    out_specs = (rep2, cache_spec)

    if not write:
        # cross-attention: no new token operands
        def body_nw(qi, qsc, cl, lengths, act):
            return body(qi, qsc, None, None, None, None, None, cl, lengths,
                        act)

        fn = jax.shard_map(body_nw, mesh=mesh,
                           in_specs=(new_tok_spec2, new_tok_spec2,
                                     cache_spec, rep1, rep1),
                           out_specs=out_specs, check_vma=False)
        return fn(qi, qsc, cl, lengths, active)

    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(qi, qsc, ki, vi, ksc, vsc, feat, cl, lengths, active)
