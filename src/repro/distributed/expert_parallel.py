"""Expert parallelism: all-to-all token dispatch under shard_map.

The TP-inside-experts default (:mod:`repro.models.moe`) always divides, but
when ``n_experts`` divides the model axis (granite: 32/16, jamba: 16/16)
true EP is available: each rank owns E/n experts, tokens travel to their
experts via ``all_to_all`` and return after the expert FFN — the classic
Switch/GShard schedule expressed in shard_map.

Numerically equivalent to the dense-dispatch reference (same router, same
capacity rule per *local* group); equality is tested on an 8-device host
mesh in tests/test_distributed.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.moe import _dispatch_group  # reference router/dispatch


def ep_moe_apply(cfg, p, x, mesh, *, axis: str = "model",
                 token_axes=("data",)):
    """MoE with expert-parallel all-to-all. x [B, S, d] (batch over dp).

    Requires cfg.n_experts % mesh.shape[axis] == 0.
    """
    n_ep = int(mesh.shape[axis])
    e = cfg.n_experts
    assert e % n_ep == 0, (e, n_ep)
    e_loc = e // n_ep

    def body(p_loc, x_loc):
        b, s, d = x_loc.shape
        t = b * s
        flat = x_loc.reshape(t, d)
        cap = int(max(t * cfg.top_k / e * cfg.capacity_factor, cfg.top_k))

        # local routing + capacity-bucketed dispatch (reference logic)
        logits = flat.astype(jnp.float32) @ p_loc["router"]
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, cfg.top_k)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, -1, keepdims=True), 1e-9)
        choice = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)
        flat_c = choice.reshape(t * cfg.top_k, e)
        pos = jnp.cumsum(flat_c, axis=0) - flat_c
        pos = jnp.sum(pos.reshape(t, cfg.top_k, e) * choice, -1)
        keep = pos < cap
        disp = (jax.nn.one_hot(pos, cap, dtype=flat.dtype)[:, :, None, :]
                * choice[..., None].astype(flat.dtype)
                * keep[..., None, None].astype(flat.dtype))
        disp = jnp.sum(disp, axis=1)                       # [T, E, cap]
        comb = disp * jnp.sum(
            gate_vals[:, :, None, None] * choice[..., None].astype(flat.dtype)
            * keep[..., None, None].astype(flat.dtype), axis=1)

        xe = jnp.einsum("tec,td->ecd", disp, flat)         # [E, cap, d]
        # ---- all-to-all: send each expert's bucket to its owner rank ----
        # a2a(tiled=False): split axis removed, receive axis inserted at
        # concat position → [e_loc, cap, n_src, d]
        xe = jax.lax.all_to_all(xe.reshape(n_ep, e_loc, cap, d), axis,
                                split_axis=0, concat_axis=2, tiled=False)
        xe = xe.transpose(0, 2, 1, 3).reshape(e_loc, n_ep * cap, d)

        # ---- local expert FFN (weights: only this rank's e_loc experts) ---
        def ffn(w, h):
            return jnp.einsum("ecd,edf->ecf", h, w)

        if cfg.gated_ffn:
            h = jax.nn.silu(ffn(p_loc["w_gate"], xe)) * ffn(p_loc["w_up"],
                                                            xe)
        else:
            h = jax.nn.gelu(ffn(p_loc["w_up"], xe))
        ye = ffn(p_loc["w_down"], h)                       # [e_loc, n·cap, d]

        # ---- return trip: chunk j goes back to token-owner rank j ----
        ye = ye.reshape(e_loc, n_ep, cap, d).transpose(1, 0, 2, 3)
        # → received [e_loc, cap, n_src(=expert-block owner), d]
        ye = jax.lax.all_to_all(ye, axis, split_axis=0, concat_axis=2,
                                tiled=False)
        ye = ye.transpose(2, 0, 1, 3).reshape(e, cap, d)
        y = jnp.einsum("tec,ecd->td", comb, ye)
        return y.reshape(b, s, d).astype(x_loc.dtype)

    pspec = {
        "router": P(),
        "w_gate": P(axis, None, None),
        "w_up": P(axis, None, None),
        "w_down": P(axis, None, None),
    }
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspec, P(token_axes, None, None)),
        out_specs=P(token_axes, None, None), check_vma=False)
    return fn(p, x)
