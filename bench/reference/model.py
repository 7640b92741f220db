"""Plain reference of the served model, in straightforward jax.numpy.

It computes, for one request, the logits the served path should have
produced at each position that emitted a token: the last prompt position
(prefill) and every decoded position after it. It imports nothing of
the program; its weights come from ``bench/weights.py`` in plain form
(ternary int8 values and per-projection scales), regenerated from the
run's seed.

What it computes, per decoder layer (the configuration file's ``arch``):

* pre-norm (RMSNorm, or LayerNorm with bias), eps 1e-6;
* BitLinear projections: the input row quantized to int8 by its absmax
  (scale = max(|x|, 1e-5) / 127, round half to even, clip ±127), an
  integer product with the ternary matrix, dequantized by the row scale
  times the projection's γ. The FFN is silu(gate) · up, whose hidden row
  crosses its own int8 barrier before the down projection;
* rotary embedding on the first and second half of each head (theta
  from the file), then q, k and v quantized to int8 per token and head;
* attention with the int8 K/V cache semantics of the system: logits
  q·k · s_q · s_k / sqrt(dh), f32 softmax, values v · s_v. Prompt
  positions attend to every earlier position. A decoded position t
  attends only to the blocks (``lop_block`` tokens) that the LOP screen
  keeps: the screen scores each valid key by pot(q)·pot(k)
  (sign · 2^floor(log2 |x|) of the int8 values), a block scores the
  maximum over its valid keys, and the comparison-free selector (64
  linear buckets between the row's least and greatest block score,
  buckets above the cut bin first, then the cut bin, each in index
  order) keeps ``k_keep`` blocks;
* after the last layer the final norm and the LM head, f32 weights and
  activations.

Every floating-point value is float32 and every matrix product runs at
``precision="highest"``: the reference sits above the precision the
published checkpoints state (float16 and bfloat16).

With ``control=True`` it is the control, the next precision below the
stated one: every floating-point weight and every activation an
operation produces is held in fp8 e4m3 (:func:`fp8`: activations with
one scale per row, weights per tensor, the embedding per row and the LM
head per column), the arithmetic between them in float32. A program
that computed in fp8 would read as this control does, and the
comparison has to fail it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

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


def _exact(x, axis=-1):
    return x


def _quant(x):
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, EPS_QUANT) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _norm(p, x, kind):
    if kind == "rmsnorm":
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + EPS_NORM) * p["g"]
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS_NORM) * p["g"] + p["b"]


def _bitlinear(x, w, gamma):
    """x f32 [T, k]; w int8 [k, n]; gamma scalar or [n]."""
    xq, xs = _quant(x)
    acc = jnp.dot(xq, w, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * gamma


def _rope(x, theta: float):
    """x [T, H, dh] → rotated on (first half, second half) pairs."""
    t, _, dh = x.shape
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _pot(x):
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


def _attend(qi, qs, ki, ks, vi, vs, n_prompt, *, block, k_keep, r):
    """One group of heads. qi int8 [G, T, dh], qs [G, T, 1]; ki/vi int8
    [G, T, dh]; ks/vs [G, T] → [G, T, dh]; ``r`` rounds what each
    operation produces (:func:`fp8` in the control)."""
    g, t, dh = qi.shape
    tpos = jnp.arange(t)[:, None]
    causal = jnp.arange(t)[None, :] <= tpos                 # [T, T]
    s = jnp.einsum("gtd,gmd->gtm", qi, ki,
                   preferred_element_type=jnp.int32).astype(jnp.float32)
    s = r(s * qs * ks[:, None, :] * dh ** -0.5)

    sc = jnp.einsum("gtd,gmd->gtm", _pot(qi), _pot(ki),
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


@functools.partial(jax.jit, static_argnames=("arch_items", "k_keep", "low"))
def _layer(x, lw, n_prompt, *, arch_items, k_keep, low):
    arch = W.thaw(arch_items)
    r = fp8 if low else _exact
    n = W.dims(arch)
    t = x.shape[0]
    h, hkv, dh = arch["n_heads"], arch["n_kv_heads"], n["hd"]

    hn = r(_norm(lw["ln1"], x, arch["norm"]))
    gcol = jnp.concatenate([jnp.full((n["q"],), lw["g_q"]),
                            jnp.full((n["kv"],), lw["g_k"]),
                            jnp.full((n["kv"],), lw["g_v"])])
    qkv = r(_bitlinear(hn, lw["wqkv"], gcol))
    q = qkv[:, :n["q"]].reshape(t, h, dh)
    k = qkv[:, n["q"]:n["q"] + n["kv"]].reshape(t, hkv, dh)
    v = qkv[:, n["q"] + n["kv"]:].reshape(t, hkv, dh)
    theta = float(arch["rope_theta"])
    q, k = r(_rope(q, theta)), r(_rope(k, theta))
    qi, qs = _quant(q)                                       # [T, H, dh]
    ki, ks = _quant(k)
    vi, vs = _quant(v)
    rep = h // hkv
    to_heads = lambda a: jnp.repeat(jnp.moveaxis(a, 1, 0), rep, axis=0)
    qi, qs = jnp.moveaxis(qi, 1, 0), jnp.moveaxis(qs, 1, 0)
    ki, vi = to_heads(ki), to_heads(vi)
    ks, vs = to_heads(ks)[..., 0], to_heads(vs)[..., 0]
    grp = HEAD_GROUP if h % HEAD_GROUP == 0 else 1
    split = lambda a: a.reshape((h // grp, grp) + a.shape[1:])
    o = jax.lax.map(
        lambda a: _attend(*a, n_prompt, block=arch["lop_block"],
                          k_keep=k_keep, r=r),
        tuple(split(a) for a in (qi, qs, ki, ks, vi, vs)))
    o = jnp.moveaxis(o.reshape(h, t, dh), 0, 1).reshape(t, n["q"])
    x = r(x + r(_bitlinear(o, lw["wo"], lw["g_o"])))

    hn = r(_norm(lw["ln2"], x, arch["norm"]))
    f = n["f"]
    gcol = jnp.concatenate([jnp.full((f,), lw["g_g"]),
                            jnp.full((f,), lw["g_u"])])
    gu = r(_bitlinear(hn, lw["wgu"], gcol))
    hid = r(jax.nn.silu(gu[:, :f]) * gu[:, f:])
    return r(x + r(_bitlinear(hid, lw["wd"], lw["g_d"])))


@functools.partial(jax.jit, static_argnames=("arch_items", "n_rows", "low"))
def _head(x, top, first, *, arch_items, n_rows, low):
    arch = W.thaw(arch_items)
    r = fp8 if low else _exact
    rows = jnp.clip(first + jnp.arange(n_rows), 0, x.shape[0] - 1)
    xr = r(_norm(top["ln_f"], x[rows], arch["norm"]))
    return r(jnp.dot(xr, top["head"], preferred_element_type=jnp.float32))


@jax.jit
def _low_weights(tree):
    """The control's weights: every float leaf in fp8 with one scale per
    tensor, the embedding's per row and the LM head's per column."""
    out = jax.tree.map(
        lambda a: fp8(a, None) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, tree)
    if "embed" in tree:
        out["embed"] = fp8(tree["embed"])
        out["head"] = fp8(tree["head"], 0)
    return out


def logits(arch: dict, seed: int, tokens, n_prompt: int, *, length: int,
           k_keep: int, n_rows: int, control: bool = False) -> np.ndarray:
    """Logits [n_rows, vocab] at positions n_prompt-1, n_prompt, ... of
    the sequence ``tokens`` (prompt ‖ served tokens), padded to
    ``length`` positions (a multiple of ``lop_block``) so that every
    request of a cell shares one compiled program. Rows past the
    sequence are not meaningful. ``control``: the fp8 control (module
    doc)."""
    tokens = np.asarray(tokens, np.int32)
    if len(tokens) > length or length % arch["lop_block"]:
        raise ValueError(f"{len(tokens)} tokens in a length-{length} pass")
    items = W.frozen(arch)
    weights = _low_weights if control else (lambda tree: tree)
    with jax.default_matmul_precision("highest"):
        data = W.seed_data(seed)
        top = weights(W.plain_top_jit(items, data))
        padded = np.zeros((length,), np.int32)
        padded[:len(tokens)] = tokens
        x = top["embed"][jnp.asarray(padded)]
        for key in W.layer_keys(data, arch["n_layers"]):
            lw = weights(W.plain_layer_jit(items, key))
            x = _layer(x, lw, jnp.int32(n_prompt), arch_items=items,
                       k_keep=k_keep, low=control)
        out = _head(x, top, jnp.int32(n_prompt - 1), arch_items=items,
                    n_rows=n_rows, low=control)
    return np.asarray(out)
