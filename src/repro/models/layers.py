"""Shared building blocks: BitLinear, norms, embeddings, rotary.

Parameters are plain nested dicts of arrays; every ``*_init`` returns
``(params, pspecs)`` where ``pspecs`` mirrors the param tree with tuples of
*logical* axes (resolved by :mod:`repro.distributed.partitioning`).

The quantized flow follows the paper: projections are BitLinear (absmean
ternary weights × absmax int8 activations, trained with STE); embeddings,
norms, router and the LM head stay high-precision (BitNet's convention).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.qlinear import is_packed, qlinear
from repro.core.quantization import rmsnorm
from repro.core.ternary import bitlinear_qat


# ---------------------------------------------------------------------------
# Linear / BitLinear
# ---------------------------------------------------------------------------

def linear_init(key, d_in: int, d_out: int, *, bias: bool = False,
                spec=("fsdp", "tp"), dtype=jnp.float32):
    w = jax.random.normal(key, (d_in, d_out), dtype) * (d_in ** -0.5)
    params = {"w": w}
    pspecs = {"w": spec}
    if bias:
        params["b"] = jnp.zeros((d_out,), dtype)
        pspecs["b"] = (spec[-1],)
    return params, pspecs


def linear_apply(params, x, *, quant: str):
    """Linear dispatch on param format:

      * serving nodes (``{"packed", "scale"}``) → the fused TINT entry
        (absmax barrier + packed-ternary GEMM + dequant epilogue in ONE
        dispatch, DESIGN.md §TINT-projection-fusion — so the same model
        code serves quantized weights),
      * training nodes (``{"w"}``) → QAT BitLinear (``quant="ternary"``)
        or plain matmul (``"bf16"``).
    """
    if is_packed(params):
        return qlinear(params, x)
    if quant == "ternary":
        y = bitlinear_qat(x, params["w"])
    else:
        y = x @ params["w"].astype(x.dtype)
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Norms (f32 reductions per the absmax barrier discipline)
# ---------------------------------------------------------------------------

def norm_init(d: int, kind: str, dtype=jnp.float32):
    if kind == "rmsnorm":
        return {"g": jnp.ones((d,), dtype)}, {"g": (None,)}
    return ({"g": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)},
            {"g": (None,), "b": (None,)})


def norm_apply(params, x, kind: str, eps: float = 1e-6):
    if kind == "rmsnorm":
        return rmsnorm(x, params["g"], eps)
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["g"] + params["b"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------

def embedding_init(key, vocab_padded: int, d: int, dtype=jnp.float32):
    e = jax.random.normal(key, (vocab_padded, d), dtype) * 0.02
    return {"table": e}, {"table": ("tp", "fsdp")}


def embedding_apply(params, tokens):
    return jnp.take(params["table"], tokens, axis=0)


def head_apply(params, x):
    """LM head (high-precision): [..., d] @ [d, V] → logits."""
    return x @ params["w"]


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float,
         freqs=None) -> jax.Array:
    """x [..., S, H, dh] (dh even), positions [..., S] → rotated x.

    ``freqs`` [dh/2] replaces the plain ``theta`` frequencies (YaRN:
    :func:`yarn_freqs`)."""
    dh = x.shape[-1]
    half = dh // 2
    if freqs is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs      # [..., S, half]
    cos = jnp.cos(ang)[..., None, :]                            # [..., S, 1, half]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin,
                           xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# YaRN (DeepSeek-V2's rotary scaling, arXiv:2309.00071)
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(dim: int, theta: float, factor: float, original_max_pos: int,
               beta_fast: float, beta_slow: float):
    """Rotary frequencies [dim/2] f32: the plain ``theta`` frequencies
    where a dimension turns more than ``beta_fast`` times over the
    original context, those divided by ``factor`` where it turns fewer
    than ``beta_slow`` times, and a linear ramp between."""
    import numpy as np

    def corr_dim(rot):
        return (dim * math.log(original_max_pos / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    exps = np.arange(0, dim, 2, dtype=np.float32) / dim
    extra = 1.0 / (theta ** exps)
    inter = 1.0 / (factor * theta ** exps)
    mask = 1.0 - ramp
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def rope_freqs(cfg):
    """The latent-attention rotary frequencies of ``cfg`` (YaRN where
    ``rope_factor`` is set), or None for the plain ``rope_theta`` ones."""
    if not cfg.rope_factor:
        return None
    if yarn_mscale(cfg.rope_factor, cfg.rope_mscale) != yarn_mscale(
            cfg.rope_factor, cfg.rope_mscale_all_dim):
        raise ValueError("YaRN with cos/sin scaled (mscale != "
                         "mscale_all_dim) is not implemented")
    return jnp.asarray(yarn_freqs(cfg.qk_rope_head_dim, cfg.rope_theta,
                                  cfg.rope_factor, cfg.rope_original_max_pos,
                                  cfg.rope_beta_fast, cfg.rope_beta_slow))


def mla_softmax_scale(cfg) -> float:
    """(qk_nope + qk_rope)^-0.5, times mscale² under YaRN with
    ``rope_mscale_all_dim`` (DeepSeek-V2: 0.11472)."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_factor and cfg.rope_mscale_all_dim:
        m = yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
        scale *= m * m
    return scale
