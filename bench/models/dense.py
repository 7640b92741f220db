"""The dense decoder: BitNet b1.58 and the LLaMA-style ternary models
(``"family": "dense"``), the default architecture of a configuration.

Everything the benchmark needs of the architecture is here, found by
the configuration (``bench/models/__init__.py`` names the interface):

* the program's ``ModelConfig`` (:func:`model_config`);
* random weights from the run's seed (``bench/weights.py`` draws them):
  :func:`plain_layer` / :func:`plain_top` in plain form for the
  reference, :func:`served_params` in the serving format of
  ``repro.serving.quantize`` (2-bit codes packed by the program's
  ``repro.core.ternary.pack_ternary``, per-column γ rows, the FFN hidden
  width zero-padded to a multiple of 128), made on the device in one
  jitted call from the same plain values. Only that path imports the
  program; layer ``l`` of the served tree and
  ``plain_layer(arch, layer_keys(seed_data(seed), n_layers)[l])`` hold
  the same numbers;
* the plain reference (:func:`logits`), from the shared pieces of
  ``bench/reference/model.py``. Per decoder layer: pre-norm; a fused QKV
  BitLinear projection; rotary on q and k (theta from the file), then
  q, k and v quantized to int8 per token and head; attention over the
  blocks the LOP screen keeps; the O projection and the residual; a
  second pre-norm and the FFN silu(gate) · up, whose hidden row crosses
  its own int8 barrier before the down projection, and the residual.
  After the last layer the final norm and the LM head, f32 weights and
  activations;
* the operations and bytes its served kernels need, from shapes (the
  rules in ``bench/cost.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import cost
from bench import weights as W
from bench.reference import model as R

KERNELS: dict = {}       # every kernel and program is in bench/kernels.json


def model_config(conf: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(name=conf["name"], **conf["arch"])


def dims(arch: dict) -> dict:
    hd = arch.get("head_dim") or arch["d_model"] // arch["n_heads"]
    q = arch["n_heads"] * hd
    kv = arch["n_kv_heads"] * hd
    return {"d": arch["d_model"], "hd": hd, "q": q, "kv": kv,
            "qkv": q + 2 * kv, "f": arch["d_ff"], "v": arch["vocab"],
            "fp": -(-arch["d_ff"] // W.FFN_ALIGN) * W.FFN_ALIGN}


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def plain_layer(arch: dict, key) -> dict:
    """One decoder layer in plain form (ternary int8 values + scales)."""
    n = dims(arch)
    ks = jax.random.split(key, 12)
    return {
        "ln1": W.norm_params(ks[0], n["d"], arch["norm"]),
        "ln2": W.norm_params(ks[1], n["d"], arch["norm"]),
        "wqkv": W.ternary(ks[2], (n["d"], n["qkv"])),
        "g_q": W.gamma(ks[3], n["d"]), "g_k": W.gamma(ks[4], n["d"]),
        "g_v": W.gamma(ks[5], n["d"]),
        "wo": W.ternary(ks[6], (n["q"], n["d"])),
        "g_o": W.gamma(ks[7], n["q"]),
        "wgu": W.ternary(ks[8], (n["d"], 2 * n["f"])),
        "g_g": W.gamma(ks[9], n["d"]), "g_u": W.gamma(ks[10], n["d"]),
        "wd": W.ternary(jax.random.fold_in(ks[11], 0), (n["f"], n["d"])),
        "g_d": W.gamma(jax.random.fold_in(ks[11], 1), n["f"]),
    }


def plain_top(arch: dict, data) -> dict:
    """Embedding table, LM head and final norm (f32)."""
    n = dims(arch)
    ke, kh, kn = jax.random.split(W.top_key(data), 3)
    return {
        "embed": W.EMBED_STD * jax.random.normal(ke, (n["v"], n["d"]),
                                                 jnp.float32),
        "head": jax.random.normal(kh, (n["d"], n["v"]), jnp.float32)
        / np.sqrt(n["d"]),
        "ln_f": W.norm_params(kn, n["d"], arch["norm"]),
    }


def _served_layer(arch: dict, key) -> dict:
    from repro.core.ternary import pack_ternary as pack
    n = dims(arch)
    p = plain_layer(arch, key)
    f, fp = n["f"], n["fp"]
    col = lambda g, w: jnp.full((w,), g, jnp.float32)
    pad_cols = lambda w: jnp.pad(w, ((0, 0), (0, fp - f)))
    gu = jnp.concatenate([pad_cols(p["wgu"][:, :f]),
                          pad_cols(p["wgu"][:, f:])], axis=1)
    return {
        "ln1": p["ln1"], "ln2": p["ln2"],
        "attn": {
            "wqkv": {"packed": pack(p["wqkv"]),
                     "scale": jnp.concatenate(
                         [col(p["g_q"], n["q"]), col(p["g_k"], n["kv"]),
                          col(p["g_v"], n["kv"])])[None]},
            "wo": {"packed": pack(p["wo"]),
                   "scale": p["g_o"].reshape(1, 1)},
        },
        "ffn": {
            "gu_packed": pack(gu),
            "gu_scale": jnp.concatenate([col(p["g_g"], fp),
                                         col(p["g_u"], fp)])[None],
            "down_packed": pack(jnp.pad(p["wd"], ((0, fp - f), (0, 0)))),
            "down_scale": p["g_d"].reshape(1, 1),
        },
    }


@functools.partial(jax.jit, static_argnums=(0,))
def _served(arch_items: tuple, data):
    arch = W.thaw(arch_items)
    layers = jax.lax.map(lambda k: _served_layer(arch, k),
                         W.layer_keys(data, arch["n_layers"]))
    top = plain_top(arch, data)
    return {"embed": {"table": top["embed"]}, "head": {"w": top["head"]},
            "ln_f": top["ln_f"], "layers": layers}


def served_params(arch: dict, seed: int) -> dict:
    """The whole serving tree, made on the device in one jitted call."""
    return _served(W.frozen(arch), W.seed_data(seed))


@functools.partial(jax.jit, static_argnums=(0,))
def plain_layer_jit(arch_items: tuple, key):
    return plain_layer(W.thaw(arch_items), key)


@functools.partial(jax.jit, static_argnums=(0,))
def plain_top_jit(arch_items: tuple, data):
    return plain_top(W.thaw(arch_items), data)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("arch_items", "k_keep", "low"))
def _layer(x, lw, n_prompt, *, arch_items, k_keep, low):
    arch = W.thaw(arch_items)
    r = R.fp8 if low else R.exact
    n = dims(arch)
    t = x.shape[0]
    h, hkv, dh = arch["n_heads"], arch["n_kv_heads"], n["hd"]

    hn = r(R.norm(lw["ln1"], x, arch["norm"]))
    gcol = jnp.concatenate([jnp.full((n["q"],), lw["g_q"]),
                            jnp.full((n["kv"],), lw["g_k"]),
                            jnp.full((n["kv"],), lw["g_v"])])
    qkv = r(R.bitlinear(hn, lw["wqkv"], gcol))
    q = qkv[:, :n["q"]].reshape(t, h, dh)
    k = qkv[:, n["q"]:n["q"] + n["kv"]].reshape(t, hkv, dh)
    v = qkv[:, n["q"] + n["kv"]:].reshape(t, hkv, dh)
    theta = float(arch["rope_theta"])
    q, k = r(R.rope(q, theta)), r(R.rope(k, theta))
    qi, qs = R.quant(q)                                      # [T, H, dh]
    ki, ks = R.quant(k)
    vi, vs = R.quant(v)
    rep = h // hkv
    to_heads = lambda a: jnp.repeat(jnp.moveaxis(a, 1, 0), rep, axis=0)
    qi, qs = jnp.moveaxis(qi, 1, 0), jnp.moveaxis(qs, 1, 0)
    ki, vi = to_heads(ki), to_heads(vi)
    ks, vs = to_heads(ks)[..., 0], to_heads(vs)[..., 0]
    grp = R.HEAD_GROUP if h % R.HEAD_GROUP == 0 else 1
    split = lambda a: a.reshape((h // grp, grp) + a.shape[1:])
    o = jax.lax.map(
        lambda a: R.attend(*a, n_prompt, block=arch["lop_block"],
                           k_keep=k_keep, r=r),
        tuple(split(a) for a in (qi, qs, ki, ks, vi, vs)))
    o = jnp.moveaxis(o.reshape(h, t, dh), 0, 1).reshape(t, n["q"])
    x = r(x + r(R.bitlinear(o, lw["wo"], lw["g_o"])))

    hn = r(R.norm(lw["ln2"], x, arch["norm"]))
    f = n["f"]
    gcol = jnp.concatenate([jnp.full((f,), lw["g_g"]),
                            jnp.full((f,), lw["g_u"])])
    gu = r(R.bitlinear(hn, lw["wgu"], gcol))
    hid = r(jax.nn.silu(gu[:, :f]) * gu[:, f:])
    return r(x + r(R.bitlinear(hid, lw["wd"], lw["g_d"])))


@functools.partial(jax.jit, static_argnames=("arch_items", "n_rows", "low"))
def _head(x, top, first, *, arch_items, n_rows, low):
    arch = W.thaw(arch_items)
    r = R.fp8 if low else R.exact
    rows = jnp.clip(first + jnp.arange(n_rows), 0, x.shape[0] - 1)
    xr = r(R.norm(top["ln_f"], x[rows], arch["norm"]))
    return r(jnp.dot(xr, top["head"], preferred_element_type=jnp.float32))


def logits(arch: dict, seed: int, tokens, n_prompt: int, *, length: int,
           k_keep: int, n_rows: int, control: bool = False) -> np.ndarray:
    """Logits [n_rows, vocab] at positions n_prompt-1, n_prompt, ... of
    the sequence ``tokens`` (prompt ‖ served tokens), padded to
    ``length`` positions (a multiple of ``lop_block``) so that every
    request of a cell shares one compiled program. Rows past the
    sequence are not meaningful. ``control``: the fp8 control
    (``bench/reference/model.py``)."""
    tokens = np.asarray(tokens, np.int32)
    if len(tokens) > length or length % arch["lop_block"]:
        raise ValueError(f"{len(tokens)} tokens in a length-{length} pass")
    items = W.frozen(arch)
    weights = R.low_weights if control else (lambda tree: tree)
    with jax.default_matmul_precision("highest"):
        data = W.seed_data(seed)
        top = weights(plain_top_jit(items, data))
        padded = np.zeros((length,), np.int32)
        padded[:len(tokens)] = tokens
        x = top["embed"][jnp.asarray(padded)]
        for key in W.layer_keys(data, arch["n_layers"]):
            lw = weights(plain_layer_jit(items, key))
            x = _layer(x, lw, jnp.int32(n_prompt), arch_items=items,
                       k_keep=k_keep, low=control)
        out = _head(x, top, jnp.int32(n_prompt - 1), arch_items=items,
                    n_rows=n_rows, low=control)
    return np.asarray(out)


# ---------------------------------------------------------------------------
# operations and bytes of the served kernels
# ---------------------------------------------------------------------------

def projections_per_layer(arch: dict, rows: int) -> dict:
    """(int8 ops, bytes) of each projection of one layer at ``rows``
    rows: the fused QKV, O, the fused FFN."""
    n = dims(arch)
    return {"qkv": cost.qlinear(rows, n["d"], n["qkv"]),
            "o": cost.qlinear(rows, n["q"], n["d"]),
            "ffn": cost.ffn(rows, n["d"], n["f"])}


def decode_projection_calls(arch: dict, rows: int) -> dict:
    """{kernel: [(ops, bytes) of each of its calls in one layer, in the
    order they repeat]} at ``rows`` rows: the qlinear kernel runs the
    fused QKV then O, the ffn kernel the fused FFN."""
    per = projections_per_layer(arch, rows)
    return {"qlinear": [per["qkv"], per["o"]], "ffn": [per["ffn"]]}


def decode_attention(arch: dict, live: int, k_keep: int,
                     use_lop: bool = True) -> tuple[float, float]:
    """(int8 ops, bytes) of one lane's decode attention in one layer at
    ``live`` valid cache positions: the screen over every live key's
    packed features, then exact attention over the live keys of the kept
    blocks."""
    n = dims(arch)
    h, hkv, dh = arch["n_heads"], arch["n_kv_heads"], n["hd"]
    block = arch["lop_block"]
    exact = live
    if use_lop and live > k_keep * block:
        # the fewest live keys k_keep blocks can hold: the partial last
        # block and k_keep - 1 whole ones
        exact = (k_keep - 1) * block + (live - (live - 1) // block * block)
    kv_row = 2 * dh + 2 * cost.F32                 # int8 K, V + 2 scales
    ops = 4.0 * h * exact * dh
    nbytes = hkv * exact * kv_row + h * dh * (1 + cost.F32)  # q int8, out f32
    if use_lop and live > k_keep * block:       # the screen can prune
        ops += 2.0 * h * live * dh
        nbytes += hkv * live * dh / 2
    return ops, nbytes


def decode_attention_calls(arch: dict, live: int, k_keep: int,
                           use_lop: bool = True) -> list:
    """[(ops, bytes, calls)] of the decode attention one lane's token
    needs at ``live``: one call in each layer."""
    return [decode_attention(arch, live, k_keep, use_lop)
            + (arch["n_layers"],)]


def ternary_params(arch: dict) -> int:
    n = dims(arch)
    per_layer = n["d"] * n["qkv"] + n["q"] * n["d"] + 3 * n["d"] * n["f"]
    return arch["n_layers"] * per_layer


def decode_token_ops(arch: dict, live: int, k_keep: int,
                     use_lop: bool = True) -> float:
    """Operations one decoded token needs: 2 per ternary weight, 2 per
    LM-head weight, and the attention of every layer at ``live``."""
    n = dims(arch)
    att, _ = decode_attention(arch, live, k_keep, use_lop)
    return (2.0 * ternary_params(arch) + 2.0 * n["d"] * n["v"]
            + arch["n_layers"] * att)
