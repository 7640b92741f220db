"""What every architecture's plain reference shares, in straightforward
jax.numpy. It imports nothing of the program.

Each architecture's module (``bench/models/<name>.py``) writes its own
forward pass, ``logits``, from these pieces, reading its weights in the
plain form that ``bench/weights.py``'s draws make from the run's seed:

* :func:`norm`: RMSNorm, or LayerNorm with bias, eps 1e-6;
* :func:`bitlinear`: a BitLinear projection: the input row quantized to
  int8 by its absmax (:func:`quant`: scale = max(|x|, 1e-5) / 127,
  round half to even, clip ±127), an integer product with the ternary
  matrix, dequantized by the row scale times the projection's γ;
* :func:`rope`: rotary embedding on the first and second half of each
  head;
* :func:`attend`: attention with the int8 K/V cache semantics of the
  system: logits q·k · s_q · s_k / sqrt(dh), f32 softmax, values v · s_v.
  Prompt positions attend to every earlier position. A decoded position
  t attends only to the blocks (``lop_block`` tokens) that the LOP
  screen keeps: the screen scores each valid key by pot(q)·pot(k)
  (:func:`pot`: sign · 2^floor(log2 |x|) of the int8 values), a block
  scores the maximum over its valid keys, and the comparison-free
  selector (:func:`select_blocks`: 64 linear buckets between the row's
  least and greatest block score, buckets above the cut bin first, then
  the cut bin, each in index order) keeps ``k_keep`` blocks
  (:func:`k_keep_blocks` of the cache's :func:`capacity_of`).

Every floating-point value is float32 and every matrix product runs at
``precision="highest"``: the reference sits above the precision the
published checkpoints state (float16 and bfloat16).

The control is the next precision below the stated one: every
floating-point weight and every activation an operation produces is
held in fp8 e4m3 (:func:`fp8`: activations with one scale per row,
weights per tensor, the embedding per row and the LM head per column,
:func:`low_weights`), the arithmetic between them in float32. A forward
pass rounds what each operation produces with ``r``: :func:`fp8` in the
control, :func:`exact` otherwise. A program that computed in fp8 would
read as this control does, and the comparison has to fail it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS_NORM = 1e-6
EPS_QUANT = 1e-5
N_BUCKETS = 64
NEG_INF = -1e30
INT32_MIN = -(2**31)
HEAD_GROUP = 4           # heads per attention block (bounds memory)


def k_keep_blocks(arch: dict, capacity: int) -> int:
    """Blocks the screen keeps in a cache of ``capacity`` tokens:
    round(lop_keep · blocks), at least 1 (Python's round)."""
    nb = capacity // arch["lop_block"]
    return max(1, min(nb, int(round(arch["lop_keep"] * nb))))


def capacity_of(arch: dict, max_len: int) -> int:
    """Cache capacity of a ``max_len`` server: max_len + 1 rounded up to
    a whole block."""
    b = arch["lop_block"]
    return -(-(max_len + 1) // b) * b


def fp8(x, axis=-1):
    """x rounded to fp8 e4m3 (3 mantissa bits, subnormals below 2^-6,
    round half to even) under a scale that maps the largest |x| along
    ``axis`` (None: the whole tensor) to 448, e4m3's largest value."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    y = x / s
    e = jnp.maximum(jnp.frexp(y)[1], -5)        # y = m · 2^e, |m| in [.5, 1)
    return jnp.ldexp(jnp.round(jnp.ldexp(y, 4 - e)), e - 4) * s


def exact(x, axis=-1):
    return x


def quant(x):
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, EPS_QUANT) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def norm(p, x, kind):
    if kind == "rmsnorm":
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + EPS_NORM) * p["g"]
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS_NORM) * p["g"] + p["b"]


def bitlinear(x, w, gamma):
    """x f32 [T, k]; w int8 [k, n]; gamma scalar or [n]."""
    xq, xs = quant(x)
    acc = jnp.dot(xq, w, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * gamma


def rope(x, theta: float):
    """x [T, H, dh] → rotated on (first half, second half) pairs."""
    t, _, dh = x.shape
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def pot(x):
    """sign(x) · 2^floor(log2 |x|) of int8 x, 0 for 0 (int8)."""
    v = jnp.abs(x.astype(jnp.int32))
    lo = ((v >= 2).astype(jnp.int32) + (v >= 4) + (v >= 8) + (v >= 16)
          + (v >= 32) + (v >= 64))
    mag = jnp.where(v == 0, 0, jnp.left_shift(1, lo))
    return (jnp.sign(x.astype(jnp.int32)) * mag).astype(jnp.int8)


def select_blocks(s, k: int):
    """Comparison-free top-k over block scores s f32 [..., nb] (-inf =
    invalid) → bool [..., nb], the blocks kept."""
    finite = jnp.isfinite(s)
    smin = jnp.min(jnp.where(finite, s, jnp.inf), -1, keepdims=True)
    smax = jnp.max(jnp.where(finite, s, -jnp.inf), -1, keepdims=True)
    span = jnp.maximum(smax - smin, 1e-9)
    bucket = jnp.clip(((s - smin) / span * N_BUCKETS).astype(jnp.int32),
                      0, N_BUCKETS - 1)
    bucket = jnp.where(finite, bucket, -1)
    cnt_ge = jnp.stack([jnp.sum(bucket >= b, -1) for b in range(N_BUCKETS)],
                       -1)
    reach = cnt_ge >= k
    bins = jnp.arange(N_BUCKETS)
    cut = jnp.where(jnp.any(reach, -1, keepdims=True),
                    jnp.max(jnp.where(reach, bins, -1), -1, keepdims=True), 0)
    above = bucket > cut
    at_cut = bucket == cut
    n_above = jnp.sum(above, -1, keepdims=True)
    rank = jnp.where(above, jnp.cumsum(above, -1) - 1,
                     jnp.where(at_cut, n_above + jnp.cumsum(at_cut, -1) - 1,
                               k))
    return rank < k


def attend(qi, qs, ki, ks, vi, vs, n_prompt, *, block, k_keep, r):
    """One group of heads. qi int8 [G, T, dh], qs [G, T, 1]; ki/vi int8
    [G, T, dh]; ks/vs [G, T] → [G, T, dh]; ``r`` rounds what each
    operation produces (:func:`fp8` in the control)."""
    g, t, dh = qi.shape
    tpos = jnp.arange(t)[:, None]
    causal = jnp.arange(t)[None, :] <= tpos                 # [T, T]
    s = jnp.einsum("gtd,gmd->gtm", qi, ki,
                   preferred_element_type=jnp.int32).astype(jnp.float32)
    s = r(s * qs * ks[:, None, :] * dh ** -0.5)

    sc = jnp.einsum("gtd,gmd->gtm", pot(qi), pot(ki),
                    preferred_element_type=jnp.int32)
    sc = jnp.where(causal, sc, INT32_MIN)
    nb = t // block
    blk = jnp.max(sc.reshape(g, t, nb, block), -1).astype(jnp.float32)
    blk = jnp.where(jnp.arange(nb)[None, :] * block <= tpos, blk, -jnp.inf)
    keep = jnp.repeat(select_blocks(blk, k_keep), block, axis=-1)
    allowed = causal & ((tpos < n_prompt) | keep)            # [G, T, T]

    s = jnp.where(allowed, s, NEG_INF)
    p = r(jax.nn.softmax(s, axis=-1))
    vf = r(vi.astype(jnp.float32) * vs[..., None])
    return r(jnp.einsum("gtm,gmd->gtd", p, vf))


@jax.jit
def low_weights(tree):
    """The control's weights: every float leaf in fp8 with one scale per
    tensor, the embedding's per row and the LM head's per column."""
    out = jax.tree.map(
        lambda a: fp8(a, None) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, tree)
    if "embed" in tree:
        out["embed"] = fp8(tree["embed"])
        out["head"] = fp8(tree["head"], 0)
    return out
