"""Convert trained master weights → the serving (deployment) format.

Every eligible projection becomes the TINT stream format: packed 2-bit
ternary codes (4 weights/byte in HBM) + one absmean scale γ — the paper's
~8× weight-memory reduction vs bf16. Embedding/head/norms/router/conv/SSM
tensors stay high-precision (BitNet's convention), as do projections whose
reduction dim is too small to pack (< 4-aligned, e.g. Mamba's tiny dt_proj
in reduced configs).

Projection-group fusion (DESIGN.md §TINT-projection-fusion)
-----------------------------------------------------------
Deployment is also where projection groups fuse into single packed
weights so one kernel dispatch replaces several:

  * self-attention ``{"wq","wk","wv","wo"}`` → ``{"wqkv", "wo"}`` — the
    QKV codes concatenate along the output axis; the node's ``scale`` is
    a per-column γ row (each column keeps its own projection's scalar γ,
    so the fused dequant is bitwise the per-projection dequant),
  * cross-attention ``xattn`` → ``{"wq", "wkv", "wo"}`` (K and V both
    consume the encoder memory; Q consumes the decoder stream, so it
    stays its own dispatch),
  * FFN ``{"w_gate","w_up","w_down"}`` → ``{"gu_packed", "gu_scale",
    "down_packed", "down_scale"}`` — gate‖up codes share one stream and
    the down projection rides the SAME launch
    (:func:`repro.kernels.ops.ffn_fused`), hidden state never touching
    HBM; the hidden width is zero-padded to ``FFN_HIDDEN_ALIGN``,
  * MoE expert stacks [E, k, n] fuse the same way with a leading expert
    axis — the whole MoE layer's expert FFNs are ONE grouped dispatch.

``fuse=False`` keeps the legacy one-node-per-projection format (every
consumer still accepts it) — the dispatch-count baseline in
benchmarks/kernels_micro.py and the fused-vs-unfused equivalence tests.

Stacked layer weights [L, k, n] pack to [L, k//4, n] (scale [L, 1, 1]) so
the serving stack still scans. Packed dicts carry no static shape metadata
(ints would become scan-traced leaves); ``k`` and segment widths are
re-derived from ``packed.shape`` / the config at apply time (see
:mod:`repro.core.qlinear`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.qlinear import (is_fused_ffn, is_packed, qlinear,  # noqa: F401 (re-export)
                                qlinear_expert)
from repro.core.ternary import pack_ternary, ternary_quantize

# The fused FFN kernel streams hidden columns in 128-lane blocks, so the
# hidden width f is padded with zero codes to a multiple of this. A zero
# column leaves the hidden vector and its absmax unchanged (every
# activation maps 0 to 0), so the padded FFN is bitwise the unpadded one.
FFN_HIDDEN_ALIGN = 128

# param-path names that stay high-precision even when 2-D
_KEEP_FP = ("head", "projector", "router", "mu", "mu_c", "u",
            "A_log", "D", "conv_w", "conv_b", "w_base", "ln_x", "table")
_EXPERT_NAMES = ("w_gate", "w_up", "w_down")


def _quantize_linear(w: jax.Array):
    """w [..., k, n] f32 → {"packed": uint8 [..., k//4, n], "scale": f32}."""
    lead = w.shape[:-2]
    k, n = w.shape[-2:]
    w2 = w.reshape(-1, k, n)

    def one(wi):
        wt, gamma = ternary_quantize(wi)
        return pack_ternary(wt), gamma.reshape(())

    packed, scale = jax.vmap(one)(w2)
    out = {"packed": packed.reshape(*lead, k // 4, n),
           "scale": scale.reshape(*lead, 1, 1)}
    _check_packed(out, k)
    return out


def _check_packed(node, k: int) -> None:
    """Deployment-format invariants the fused kernels rely on: packed k
    is 4-aligned uint8 codes; scales are one scalar γ per code stream
    (a fused node broadcasts those scalars to a per-column row)."""
    n = node["packed"].shape[-1]
    assert node["packed"].dtype == jnp.uint8, node["packed"].dtype
    assert k % 4 == 0 and node["packed"].shape[-2] * 4 == k, \
        (node["packed"].shape, k)
    s = node["scale"]
    assert s.dtype == jnp.float32 and s.shape[-2] == 1 \
        and s.shape[-1] in (1, n), (s.dtype, s.shape, n)


def _concat_packed(parts):
    """Per-projection packed nodes → one fused node, γ per column."""
    packed = jnp.concatenate([p["packed"] for p in parts], axis=-1)
    scale = jnp.concatenate(
        [jnp.broadcast_to(p["scale"],
                          p["scale"].shape[:-1] + (p["packed"].shape[-1],))
         for p in parts], axis=-1)
    out = {"packed": packed, "scale": scale}
    _check_packed(out, packed.shape[-2] * 4)
    return out


def _pad_codes(node, size: int, axis: int):
    """Zero-code padding of a packed node's ``axis`` to ``size`` (γ is
    one scalar per stream, so the scale needs none)."""
    packed = node["packed"]
    widths = [(0, 0)] * packed.ndim
    widths[axis] = (0, size - packed.shape[axis])
    return dict(node, packed=jnp.pad(packed, widths))


def _eligible(name: str, k: int, quant: str) -> bool:
    return quant == "ternary" and name not in _KEEP_FP and k % 4 == 0 and k >= 16


def _quantize_node(name: str, node, quant: str):
    """One training linear dict {"w", "b"?} → serving node (packed or fp)."""
    if _eligible(name, node["w"].shape[-2], quant):
        out = _quantize_linear(node["w"])
        if "b" in node:
            out["b"] = node["b"]
        return out
    return dict(node)


def _fuse_attn(node, quant: str, fuse_q: bool):
    """Attention dict → fused serving dict, or None when ineligible."""
    names = ("wq", "wk", "wv") if fuse_q else ("wk", "wv")
    subs = [node.get(nm) for nm in names]
    if not all(isinstance(s, dict) and "w" in s
               and not isinstance(s["w"], dict) for s in subs):
        return None
    k = subs[0]["w"].shape[-2]
    if not all(s["w"].shape[-2] == k and _eligible(nm, k, quant)
               for nm, s in zip(names, subs)):
        return None
    has_b = ["b" in s for s in subs]
    if any(has_b) != all(has_b):
        return None
    fused = _concat_packed([_quantize_linear(s["w"]) for s in subs])
    if all(has_b):
        fused["b"] = jnp.concatenate([s["b"] for s in subs], axis=-1)
    out = {("wqkv" if fuse_q else "wkv"): fused}
    if not fuse_q:
        out["wq"] = _quantize_node("wq", node["wq"], quant)
    out["wo"] = _quantize_node("wo", node["wo"], quant)
    return out


def _fuse_ffn(node, quant: str):
    """FFN dict (dense {"w_*": {"w"}} or MoE raw [E, k, n] stacks + router)
    → whole-FFN serving node, or None when ineligible."""
    def _w(nm):
        sub = node.get(nm)
        if isinstance(sub, dict):
            return sub["w"] if "w" in sub and "b" not in sub else None
        return sub
    wu, wd = _w("w_up"), _w("w_down")
    if wu is None or wd is None:
        return None
    gated = "w_gate" in node
    wg = _w("w_gate") if gated else None
    if gated and wg is None:
        return None
    d, f = wu.shape[-2], wd.shape[-2]
    if not (_eligible("w_up", d, quant) and _eligible("w_down", f, quant)
            and wu.shape[-1] == f and (not gated or wg.shape[-2:] ==
                                       wu.shape[-2:])):
        return None
    fp = -(-f // FFN_HIDDEN_ALIGN) * FFN_HIDDEN_ALIGN
    parts = [_pad_codes(_quantize_linear(w), fp, axis=-1)
             for w in ([wg] if gated else []) + [wu]]
    gu = _concat_packed(parts)
    down = _pad_codes(_quantize_linear(wd), fp // 4, axis=-2)
    out = {key: val for key, val in node.items() if key not in
           ("w_gate", "w_up", "w_down")}           # router etc. stay fp
    out.update({"gu_packed": gu["packed"], "gu_scale": gu["scale"],
                "down_packed": down["packed"],
                "down_scale": down["scale"]})
    return out


def quantize_params(cfg, params, *, fuse: bool = True):
    """Training param tree → serving tree (same structure, linears packed).

    ``fuse=True`` (the default) additionally fuses projection groups —
    QKV / cross-KV / gate·up·down / grouped experts — into single packed
    streams so each group is one kernel dispatch (module docstring).
    """
    def walk(path, node):
        if isinstance(node, dict):
            name = path[-1] if path else ""
            if fuse and name in ("attn", "xattn"):
                fused = _fuse_attn(node, cfg.quant, fuse_q=name == "attn")
                if fused is not None:
                    # unrecognized attention extras (q/k norms, sinks, …)
                    # walk through unchanged-structure quantization
                    out = {key: walk(path + (key,), val)
                           for key, val in node.items()
                           if key not in ("wq", "wk", "wv", "wo")}
                    out.update(fused)
                    return out
            if fuse and "w_up" in node and "w_down" in node:
                fused = _fuse_ffn(node, cfg.quant)
                if fused is not None:
                    return fused
            if "w" in node and not isinstance(node["w"], dict):
                return _quantize_node(path[-1] if path else "", node,
                                      cfg.quant)
            return {key: walk(path + (key,), val)
                    for key, val in node.items()}
        # raw arrays: MoE expert stacks [L, E, k, n] quantize as well
        if (node.ndim >= 2 and path and path[-1] in _EXPERT_NAMES
                and _eligible(path[-1], node.shape[-2], cfg.quant)):
            return _quantize_linear(node)
        return node

    return walk((), params)


def build_serving_params(cfg, key):
    """The serving tree of ``init_params(cfg, key)``, built without its
    f32 master tree.

    Bitwise ``quantize_params(cfg, init_params(cfg, key)[0])`` under
    jit, but the f32 weights of at most one layer exist at a time: the
    unstacked leaves are made and quantized under one jit, then each
    layer of each stacked group under another, from the key
    ``stacked_init`` splits for that layer. The master tree of bitnet-3b
    alone is 13.7 GB, near all of one 16 GB chip.
    """
    from repro.models.transformer import init_unstacked, stacked_groups

    qp = jax.jit(lambda k: quantize_params(cfg, init_unstacked(cfg, k)[0]))(
        key)
    for name, (layer_init, gkey, n) in stacked_groups(cfg, key).items():
        def one(k, name=name, layer_init=layer_init):
            layer = jax.tree.map(lambda a: a[None], layer_init(k)[0])
            return quantize_params(cfg, {name: layer})[name]
        one = jax.jit(one)
        layers = [one(k) for k in jax.random.split(gkey, n)]
        qp[name] = jax.tree.map(lambda *xs: jnp.concatenate(xs), *layers)
        del layers
    return qp
