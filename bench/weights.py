"""Random weights of a configuration, made from the run's seed.

One generator serves both sides of the comparison that decides
``correct``:

* :func:`plain_layer` gives one layer in plain form: ternary values in
  {-1, 0, +1} as int8, one absmean-style scale γ per projection, and the
  norm parameters. The plain reference (``bench/reference``) reads only
  this form.
* :func:`served_params` gives the whole model in the serving format of
  ``repro.serving.quantize`` (2-bit codes packed by the program's
  ``repro.core.ternary.pack_ternary``, per-column γ rows, the FFN hidden
  width zero-padded to a multiple of 128), made on the device in one
  jitted call from the same plain values. Only this function imports
  the program, so the reference, which reads the plain form, does not.

Both draw every value from the same per-layer keys, so layer ``l`` of
the served tree and ``plain_layer(arch, layer_keys(seed_data(seed),
n_layers)[l])`` hold the same numbers. A projection of reduction width k gets
γ = 0.8 / sqrt(k) times a factor in [0.9, 1.1] drawn from the seed (the
absmean of a unit-variance init of the same width), so activations keep
their scale through the stack and every projection's γ differs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

FFN_ALIGN = 128          # the serving format's FFN hidden-width multiple
EMBED_STD = 0.02


def root_key(seed: int):
    """A threefry key from all 64 bits of ``seed`` (PRNGKey keeps 32)."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    data = np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(data), impl="threefry2x32")


def seed_data(seed: int):
    """The raw uint32 pair of :func:`root_key` (a jit argument)."""
    return jax.random.key_data(root_key(seed))


def _split(data):
    """→ (embed/head/final-norm key, key of the layer keys)."""
    top, layers = jax.random.split(
        jax.random.wrap_key_data(data, impl="threefry2x32"))
    return top, layers


def layer_keys(data, n_layers: int):
    return jax.random.split(_split(data)[1], n_layers)


def frozen(arch: dict) -> tuple:
    """A hashable form of the scalar entries of ``arch`` (a static jit
    argument); :func:`thaw` inverts it."""
    return tuple(sorted((k, v) for k, v in arch.items()
                        if not isinstance(v, (dict, list))))


def thaw(items: tuple) -> dict:
    return dict(items)


def dims(arch: dict) -> dict:
    hd = arch.get("head_dim") or arch["d_model"] // arch["n_heads"]
    q = arch["n_heads"] * hd
    kv = arch["n_kv_heads"] * hd
    return {"d": arch["d_model"], "hd": hd, "q": q, "kv": kv,
            "qkv": q + 2 * kv, "f": arch["d_ff"], "v": arch["vocab"],
            "fp": -(-arch["d_ff"] // FFN_ALIGN) * FFN_ALIGN}


def _gamma(key, k: int):
    return (0.8 / np.sqrt(k)) * jax.random.uniform(key, (), jnp.float32,
                                                   0.9, 1.1)


def _norm(key, d: int, kind: str) -> dict:
    kg, kb = jax.random.split(key)
    out = {"g": 1.0 + 0.05 * jax.random.normal(kg, (d,), jnp.float32)}
    if kind == "layernorm":
        out["b"] = 0.05 * jax.random.normal(kb, (d,), jnp.float32)
    return out


def plain_layer(arch: dict, key) -> dict:
    """One decoder layer in plain form (ternary int8 values + scales)."""
    n = dims(arch)
    ks = jax.random.split(key, 12)
    tern = lambda k, shape: jax.random.randint(k, shape, -1, 2, jnp.int8)
    return {
        "ln1": _norm(ks[0], n["d"], arch["norm"]),
        "ln2": _norm(ks[1], n["d"], arch["norm"]),
        "wqkv": tern(ks[2], (n["d"], n["qkv"])),
        "g_q": _gamma(ks[3], n["d"]), "g_k": _gamma(ks[4], n["d"]),
        "g_v": _gamma(ks[5], n["d"]),
        "wo": tern(ks[6], (n["q"], n["d"])), "g_o": _gamma(ks[7], n["q"]),
        "wgu": tern(ks[8], (n["d"], 2 * n["f"])),
        "g_g": _gamma(ks[9], n["d"]), "g_u": _gamma(ks[10], n["d"]),
        "wd": tern(jax.random.fold_in(ks[11], 0), (n["f"], n["d"])),
        "g_d": _gamma(jax.random.fold_in(ks[11], 1), n["f"]),
    }


def plain_top(arch: dict, data) -> dict:
    """Embedding table, LM head and final norm (f32)."""
    n = dims(arch)
    ke, kh, kn = jax.random.split(_split(data)[0], 3)
    return {
        "embed": EMBED_STD * jax.random.normal(ke, (n["v"], n["d"]),
                                               jnp.float32),
        "head": jax.random.normal(kh, (n["d"], n["v"]), jnp.float32)
        / np.sqrt(n["d"]),
        "ln_f": _norm(kn, n["d"], arch["norm"]),
    }


# ---------------------------------------------------------------------------
# the serving format
# ---------------------------------------------------------------------------

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
    arch = thaw(arch_items)
    layers = jax.lax.map(lambda k: _served_layer(arch, k),
                         layer_keys(data, arch["n_layers"]))
    top = plain_top(arch, data)
    return {"embed": {"table": top["embed"]}, "head": {"w": top["head"]},
            "ln_f": top["ln_f"], "layers": layers}


def served_params(arch: dict, seed: int) -> dict:
    """The whole serving tree, made on the device in one jitted call."""
    return _served(frozen(arch), seed_data(seed))


@functools.partial(jax.jit, static_argnums=(0,))
def plain_layer_jit(arch_items: tuple, key):
    return plain_layer(thaw(arch_items), key)


@functools.partial(jax.jit, static_argnums=(0,))
def plain_top_jit(arch_items: tuple, data):
    return plain_top(thaw(arch_items), data)
