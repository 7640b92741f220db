"""DeepSeek-V2's block (``"model": "deepseek_v2"``): multi-head latent
attention (MLA) with YaRN rotary, a leading dense layer, and then
routed experts (softmax top-k, no renormalisation) beside shared ones.

Everything the benchmark needs of the architecture is here, found by
the configuration (``bench/models/__init__.py`` names the interface):

* the program's ``ModelConfig`` (:func:`model_config`);
* random weights from the run's seed (``bench/weights.py`` draws them):
  :func:`plain_layer` (and ``dense.plain_top``: embedding, LM head,
  final norm) in plain form for the reference, :func:`served_params`
  in the serving format of ``repro.serving.quantize`` (q ‖ kv_a one
  packed node with a γ per column, zero-padded to a multiple of 128
  columns; kv_b's codes split per head into W_UKᵀ and W_UV; experts
  stacked on a leading axis; FFN hidden widths zero-padded to a
  multiple of 128), made on the device in one jitted call that builds and packs
  one layer, and within it one expert, at a time. Only that path
  imports the program; layer ``l`` of the served trees (``dense_layers``
  then ``layers``) and ``plain_layer(arch, layer_keys(seed_data(seed),
  n_layers)[l], moe=l >= first_k_dense)`` hold the same numbers;
* the plain reference (:func:`logits`), the published equations in
  float32 at ``highest``, from the shared pieces of
  ``bench/reference/model.py``. Per layer: pre-norm; q = BitLinear(h);
  c ‖ k_rope = BitLinear(h) (kv_a); c through its RMSNorm; YaRN rotary
  on q's rope part and on k_rope; the row c ‖ k_rope quantized to int8
  with one scale (the served cache row); k_nope and v per head from
  kv_b of that int8 row (its scale and kv_b's γ); scores q_nope·k_nope +
  q_rope·k_rope at the YaRN softmax scale; decoded positions attend to
  the blocks the LOP screen keeps, the screen scoring pot of the
  served int8 absorbed query (q_nope·W_UKᵀ ‖ q_rope, through its own
  int8 barrier) against pot of the int8 rows; o_proj; then the dense
  FFN (layer 0) or the router's softmax over every expert, the top-k
  gates as they are, all experts computed densely and weighted by the
  gates (zero off the top k), plus the shared experts' FFN. Each
  BitLinear's input row crosses its int8 barrier, as served. Final norm
  and the f32 LM head;
* the operations and bytes its served kernels need, from shapes (the
  rules in ``bench/cost.py``), and the ``moe`` and ``mla`` counts the
  readers of ``moe_experts_roofline`` and ``mla_attention_roofline``
  take.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import cost
from bench import weights as W
from bench.models import dense
from bench.reference import model as R

KERNELS: dict = {}       # every kernel and program is in bench/kernels.json


def model_config(conf: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(name=conf["name"], **conf["arch"])


def dims(arch: dict) -> dict:
    h = arch["n_heads"]
    dn, dr = arch["qk_nope_head_dim"], arch["qk_rope_head_dim"]
    dc, dv = arch["kv_lora_rank"], arch["v_head_dim"]
    fe = arch["d_expert"]
    pad = lambda f: -(-f // W.FFN_ALIGN) * W.FFN_ALIGN
    return {"d": arch["d_model"], "h": h, "dn": dn, "dr": dr, "dc": dc,
            "dv": dv, "dl": dc + dr, "q": h * (dn + dr),
            "kb": h * (dn + dv), "o": h * dv, "f": arch["d_ff"],
            "fp": pad(arch["d_ff"]), "e": arch["n_experts"],
            "k": arch["top_k"], "fe": fe, "fep": pad(fe),
            "fs": arch["n_shared_experts"] * fe,
            "fsp": pad(arch["n_shared_experts"] * fe), "v": arch["vocab"],
            "dense": arch["first_k_dense"],
            "moe": arch["n_layers"] - arch["first_k_dense"]}


# ---------------------------------------------------------------------------
# YaRN, transcribed from the published DeepSeek-V2 modelling code
# ---------------------------------------------------------------------------

def _yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * np.log(scale) + 1.0


def yarn_inv_freq(arch: dict) -> np.ndarray:
    """DeepseekV2YarnRotaryEmbedding's inv_freq, f32 [dr/2]."""
    dim, base = arch["qk_rope_head_dim"], float(arch["rope_theta"])
    factor = arch["rope_factor"]
    orig = arch["rope_original_max_pos"]

    def find_dim(rot):
        return (dim * np.log(orig / (rot * 2 * np.pi))) / (2 * np.log(base))

    low = max(int(np.floor(find_dim(arch["rope_beta_fast"]))), 0)
    high = min(int(np.ceil(find_dim(arch["rope_beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    exps = np.arange(0, dim, 2, dtype=np.float32) / dim
    freq_extra = 1.0 / (base ** exps)
    freq_inter = 1.0 / (factor * base ** exps)
    inv_freq_mask = 1.0 - ramp
    return (freq_inter * (1 - inv_freq_mask)
            + freq_extra * inv_freq_mask).astype(np.float32)


def softmax_scale(arch: dict) -> float:
    """q_head_dim^-0.5 · mscale(factor, mscale_all_dim)²."""
    s = (arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"]) ** -0.5
    m = _yarn_mscale(arch["rope_factor"], arch["rope_mscale_all_dim"])
    return s * m * m


def rope_yarn(x, inv_freq):
    """x [T, H, dr] rotated on (first half, second half) pairs at YaRN's
    frequencies; cos and sin scaled by mscale / mscale_all_dim = 1."""
    t = x.shape[0]
    half = x.shape[-1] // 2
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _gammas(key, n: int, k: int):
    """``n`` projections' scales at reduction width ``k``."""
    return (0.8 / np.sqrt(k)) * jax.random.uniform(key, (n,), jnp.float32,
                                                   0.9, 1.1)


def plain_expert(arch: dict, key) -> dict:
    """One routed expert: gate ‖ up [d, 2fe], down [fe, d] (ternary)."""
    n = dims(arch)
    ka, kb = jax.random.split(key)
    return {"wgu": W.ternary(ka, (n["d"], 2 * n["fe"])),
            "wd": W.ternary(kb, (n["fe"], n["d"]))}


def _ffn_plain(key, d: int, f: int) -> dict:
    ks = jax.random.split(key, 5)
    return {"wgu": W.ternary(ks[0], (d, 2 * f)),
            "g_g": W.gamma(ks[1], d), "g_u": W.gamma(ks[2], d),
            "wd": W.ternary(ks[3], (f, d)), "g_d": W.gamma(ks[4], f)}


def _attn_plain(arch: dict, key) -> dict:
    n = dims(arch)
    ks = jax.random.split(key, 11)
    return {
        "ln1": W.norm_params(ks[0], n["d"], "rmsnorm"),
        "ln2": W.norm_params(ks[1], n["d"], "rmsnorm"),
        "wq": W.ternary(ks[2], (n["d"], n["q"])),
        "g_q": W.gamma(ks[3], n["d"]),
        "wkva": W.ternary(ks[4], (n["d"], n["dl"])),
        "g_kva": W.gamma(ks[5], n["d"]),
        "kv_norm": W.norm_params(ks[6], n["dc"], "rmsnorm"),
        "wkb": W.ternary(ks[7], (n["dc"], n["kb"])),
        "g_kb": W.gamma(ks[8], n["dc"]),
        "wo": W.ternary(ks[9], (n["o"], n["d"])),
        "g_o": W.gamma(ks[10], n["o"]),
    }


def _moe_keys(arch: dict, key):
    """(router, expert γs, shared FFN, per-expert keys)."""
    kr, kg, ks, ke = jax.random.split(jax.random.fold_in(key, 1), 4)
    return kr, kg, ks, jax.random.split(ke, arch["n_experts"])


def plain_layer(arch: dict, key, moe: bool) -> dict:
    """One layer in plain form: latent attention, then the dense FFN
    (``moe`` False) or the router, the experts stacked [E, ...] and the
    shared experts."""
    n = dims(arch)
    p = _attn_plain(arch, key)
    if not moe:
        p["ffn"] = _ffn_plain(jax.random.fold_in(key, 1), n["d"], n["f"])
        return p
    kr, kg, ks, kexp = _moe_keys(arch, key)
    g = jax.random.split(kg, 3)
    p["moe"] = {
        "router": jax.random.normal(kr, (n["d"], n["e"]), jnp.float32)
        / np.sqrt(n["d"]),
        "g_g": _gammas(g[0], n["e"], n["d"]),
        "g_u": _gammas(g[1], n["e"], n["d"]),
        "g_d": _gammas(g[2], n["e"], n["fe"]),
        "shared": _ffn_plain(ks, n["d"], n["fs"]),
        **jax.vmap(lambda k: plain_expert(arch, k))(kexp),
    }
    return p


def _pack_ffn(pack, p: dict, f: int, fp: int, g_g, g_u, g_d) -> dict:
    """Gate ‖ up [d, 2f] and down [f, d] codes, the hidden width padded
    to fp, as the whole-FFN serving node."""
    pad_cols = lambda w: jnp.pad(w, ((0, 0), (0, fp - f)))
    gu = jnp.concatenate([pad_cols(p["wgu"][:, :f]),
                          pad_cols(p["wgu"][:, f:])], axis=1)
    return {"gu_packed": pack(gu),
            "gu_scale": jnp.concatenate([jnp.full((fp,), g_g),
                                         jnp.full((fp,), g_u)])[None],
            "down_packed": pack(jnp.pad(p["wd"], ((0, fp - f), (0, 0)))),
            "down_scale": jnp.reshape(g_d, (1, 1))}


def _served_layer(arch: dict, key, moe: bool) -> dict:
    from repro.core.ternary import pack_ternary as pack
    n = dims(arch)
    a = _attn_plain(arch, key)
    col = lambda g, w: jnp.full((w,), g, jnp.float32)
    kb = a["wkb"].reshape(n["dc"], n["h"], -1).transpose(1, 0, 2)
    head_g = jnp.full((n["h"], 1, 1), a["g_kb"], jnp.float32)
    q_kva = n["q"] + n["dl"]
    pad = -(-q_kva // W.FFN_ALIGN) * W.FFN_ALIGN - q_kva   # zero columns
    out = {
        "ln1": a["ln1"], "ln2": a["ln2"],
        "attn": {
            "wq_kva": {"packed": pack(jnp.pad(
                jnp.concatenate([a["wq"], a["wkva"]], 1),
                ((0, 0), (0, pad)))),
                       "scale": jnp.concatenate(
                           [col(a["g_q"], n["q"]), col(a["g_kva"], n["dl"]),
                            jnp.zeros((pad,), jnp.float32)])[None]},
            "kv_norm": a["kv_norm"],
            "wuk": {"packed": jax.vmap(pack)(
                kb[..., :n["dn"]].transpose(0, 2, 1)), "scale": head_g},
            "wuv": {"packed": jax.vmap(pack)(kb[..., n["dn"]:]),
                    "scale": head_g},
            "wo": {"packed": pack(a["wo"]),
                   "scale": a["g_o"].reshape(1, 1)},
        },
    }
    if not moe:
        f = _ffn_plain(jax.random.fold_in(key, 1), n["d"], n["f"])
        out["ffn"] = _pack_ffn(pack, f, n["f"], n["fp"], f["g_g"], f["g_u"],
                               f["g_d"])
        return out
    kr, kg, ks, kexp = _moe_keys(arch, key)
    g = jax.random.split(kg, 3)
    g_g, g_u = _gammas(g[0], n["e"], n["d"]), _gammas(g[1], n["e"], n["d"])
    g_d = _gammas(g[2], n["e"], n["fe"])
    # one expert at a time: its int8 values never exist for all 64 at once
    experts = jax.lax.map(
        lambda args: _pack_ffn(pack, plain_expert(arch, args[0]), n["fe"],
                               n["fep"], *args[1:]),
        (kexp, g_g, g_u, g_d))
    s = _ffn_plain(ks, n["d"], n["fs"])
    out["moe"] = {
        "router": jax.random.normal(kr, (n["d"], n["e"]), jnp.float32)
        / np.sqrt(n["d"]),
        **experts,
        "shared": _pack_ffn(pack, s, n["fs"], n["fsp"], s["g_g"], s["g_u"],
                            s["g_d"]),
    }
    return out


@functools.partial(jax.jit, static_argnums=(0,))
def _served(arch_items: tuple, data):
    arch = W.thaw(arch_items)
    keys = W.layer_keys(data, arch["n_layers"])
    k0 = arch["first_k_dense"]
    top = dense.plain_top(arch, data)
    return {"embed": {"table": top["embed"]}, "head": {"w": top["head"]},
            "ln_f": top["ln_f"],
            "dense_layers": jax.lax.map(
                lambda k: _served_layer(arch, k, False), keys[:k0]),
            "layers": jax.lax.map(
                lambda k: _served_layer(arch, k, True), keys[k0:])}


def served_params(arch: dict, seed: int) -> dict:
    """The whole serving tree, made on the device in one jitted call."""
    return _served(W.frozen(arch), W.seed_data(seed))


@functools.partial(jax.jit, static_argnums=(0, 2))
def plain_layer_jit(arch_items: tuple, key, moe: bool):
    return plain_layer(W.thaw(arch_items), key, moe)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _bitlinear_heads(x, w, gamma):
    """Per-head BitLinear: x f32 [T, H, k] (a barrier per row and head),
    w int8 [H, k, n] → [T, H, n]."""
    xq, xs = R.quant(x)
    acc = jnp.einsum("thk,hkn->thn", xq, w, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * gamma


def _attention(x, lw, n_prompt, *, arch, k_keep, r):
    n = dims(arch)
    t = x.shape[0]
    h, dn, dc = n["h"], n["dn"], n["dc"]
    inv_freq = yarn_inv_freq(arch)

    hn = r(R.norm(lw["ln1"], x, "rmsnorm"))
    q = r(R.bitlinear(hn, lw["wq"], lw["g_q"])).reshape(t, h, -1)
    kva = r(R.bitlinear(hn, lw["wkva"], lw["g_kva"]))
    c = r(R.norm(lw["kv_norm"], kva[:, :dc], "rmsnorm"))
    q_rope = r(rope_yarn(q[..., dn:], inv_freq))
    k_rope = r(rope_yarn(kva[:, None, dc:], inv_freq))[:, 0]
    li, ls = R.quant(jnp.concatenate([c, k_rope], -1))    # the cached row
    # k_nope and v from kv_b of the int8 row (its scale, kv_b's γ)
    kv = jnp.dot(li[:, :dc], lw["wkb"], preferred_element_type=jnp.int32)
    kv = r(kv.astype(jnp.float32) * ls * lw["g_kb"]).reshape(t, h, -1)
    k_rope = li[:, dc:].astype(jnp.float32) * ls
    s = (jnp.einsum("thd,mhd->htm", q[..., :dn], kv[..., :dn])
         + jnp.einsum("thd,md->htm", q_rope, k_rope))
    s = r(s * softmax_scale(arch))

    # the screen: pot of the served absorbed int8 query · pot of the rows
    w_uk = lw["wkb"].reshape(dc, h, -1)[..., :dn].transpose(1, 2, 0)
    qa = _bitlinear_heads(q[..., :dn], w_uk, lw["g_kb"])
    qi, _ = R.quant(jnp.concatenate([qa, q_rope], -1))     # [T, H, dl]
    sc = jnp.einsum("thd,md->htm", R.pot(qi), R.pot(li),
                    preferred_element_type=jnp.int32)

    block = arch["lop_block"]
    tpos = jnp.arange(t)[:, None]
    causal = jnp.arange(t)[None, :] <= tpos
    sc = jnp.where(causal, sc, R.INT32_MIN)
    nb = t // block
    blk = jnp.max(sc.reshape(h, t, nb, block), -1).astype(jnp.float32)
    blk = jnp.where(jnp.arange(nb)[None, :] * block <= tpos, blk, -jnp.inf)
    keep = jnp.repeat(R.select_blocks(blk, k_keep), block, axis=-1)
    allowed = causal & ((tpos < n_prompt) | keep)           # [H, T, T]
    p = r(jax.nn.softmax(jnp.where(allowed, s, R.NEG_INF), axis=-1))
    o = r(jnp.einsum("htm,mhd->thd", p, kv[..., dn:]))
    return r(R.bitlinear(o.reshape(t, -1), lw["wo"], lw["g_o"]))


def _ffn(hn, p, f, r):
    """The gated FFN of width f: both BitLinear barriers, as served."""
    gcol = jnp.concatenate([jnp.full((f,), p["g_g"]),
                            jnp.full((f,), p["g_u"])])
    gu = r(R.bitlinear(hn, p["wgu"], gcol))
    hid = r(jax.nn.silu(gu[:, :f]) * gu[:, f:])
    return r(R.bitlinear(hid, p["wd"], p["g_d"]))


def _experts(hn, m, arch, r):
    """Router softmax, top-k gates (not renormalised), every expert
    computed densely and weighted by its gate (0 off the top k), plus
    the shared experts."""
    n = dims(arch)
    t, fe = hn.shape[0], n["fe"]
    probs = r(jax.nn.softmax(jnp.dot(hn, m["router"]), axis=-1))
    gate, idx = jax.lax.top_k(probs, n["k"])
    gates = jnp.zeros((t, n["e"]), jnp.float32).at[
        jnp.arange(t)[:, None], idx].set(gate)
    xq, xs = R.quant(hn)
    gu = jnp.einsum("td,edf->etf", xq, m["wgu"],
                    preferred_element_type=jnp.int32).astype(jnp.float32)
    gcol = jnp.concatenate([jnp.repeat(m["g_g"][:, None], fe, 1),
                            jnp.repeat(m["g_u"][:, None], fe, 1)], 1)
    gu = r(gu * xs[None] * gcol[:, None, :])
    hid = r(jax.nn.silu(gu[..., :fe]) * gu[..., fe:])
    hq, hs = R.quant(hid)
    down = jnp.einsum("etf,efd->etd", hq, m["wd"],
                      preferred_element_type=jnp.int32).astype(jnp.float32)
    down = r(down * hs * m["g_d"][:, None, None])
    routed = r(jnp.einsum("te,etd->td", gates, down))
    return r(routed + _ffn(hn, m["shared"], n["fs"], r))


@functools.partial(jax.jit, static_argnames=("arch_items", "k_keep", "low",
                                             "moe"))
def _layer(x, lw, n_prompt, *, arch_items, k_keep, low, moe):
    arch = W.thaw(arch_items)
    r = R.fp8 if low else R.exact
    x = r(x + _attention(x, lw, n_prompt, arch=arch, k_keep=k_keep, r=r))
    hn = r(R.norm(lw["ln2"], x, "rmsnorm"))
    if moe:
        y = _experts(hn, lw["moe"], arch, r)
    else:
        y = _ffn(hn, lw["ffn"], arch["d_ff"], r)
    return r(x + y)


def logits(arch: dict, seed: int, tokens, n_prompt: int, *, length: int,
           k_keep: int, n_rows: int, control: bool = False) -> np.ndarray:
    """Logits [n_rows, vocab] at positions n_prompt-1, n_prompt, ... of
    the sequence ``tokens`` (prompt ‖ served tokens), padded to
    ``length`` positions (a multiple of ``lop_block``). Each layer's
    weights are made again from the seed inside the layer loop.
    ``control``: the fp8 control (``bench/reference/model.py``)."""
    tokens = np.asarray(tokens, np.int32)
    if len(tokens) > length or length % arch["lop_block"]:
        raise ValueError(f"{len(tokens)} tokens in a length-{length} pass")
    items = W.frozen(arch)
    weights = R.low_weights if control else (lambda tree: tree)
    with jax.default_matmul_precision("highest"):
        data = W.seed_data(seed)
        top = weights(dense.plain_top_jit(items, data))
        padded = np.zeros((length,), np.int32)
        padded[:len(tokens)] = tokens
        x = top["embed"][jnp.asarray(padded)]
        for i, key in enumerate(W.layer_keys(data, arch["n_layers"])):
            moe = i >= arch["first_k_dense"]
            lw = weights(plain_layer_jit(items, key, moe))
            x = _layer(x, lw, jnp.int32(n_prompt), arch_items=items,
                       k_keep=k_keep, low=control, moe=moe)
        out = dense._head(x, top, jnp.int32(n_prompt - 1),
                          arch_items=items, n_rows=n_rows, low=control)
    return np.asarray(out)


# ---------------------------------------------------------------------------
# operations and bytes of the served kernels
# ---------------------------------------------------------------------------

def distinct_experts(arch: dict, rows: float) -> float:
    """Expected distinct experts ``rows`` routed rows touch when each
    picks top_k of n_experts near uniformly (random weights)."""
    e, k = arch["n_experts"], arch["top_k"]
    return e * (1.0 - (1.0 - k / e) ** rows)


def routed_experts(arch: dict, rows: float) -> tuple[float, float]:
    """(int8 ops, bytes) of one layer's routed experts in a step of
    ``rows`` rows: each row through its top_k experts; the packed codes
    of the distinct experts the rows touch, and the rows in and out."""
    n = dims(arch)
    per = 3.0 * n["d"] * n["fe"]
    ops = 2.0 * rows * n["k"] * per
    nbytes = distinct_experts(arch, rows) * per / 4 \
        + 2 * rows * n["d"] * cost.F32
    return ops, nbytes


def moe_layer(arch: dict, rows: float) -> tuple[float, float]:
    """(int8 ops, bytes) of everything marked ``moe_experts`` in one MoE
    layer of a step of ``rows`` rows: the routed experts and the shared
    experts' FFN (``cost.ffn``)."""
    n = dims(arch)
    r_ops, r_bytes = routed_experts(arch, rows)
    s_ops, s_bytes = cost.ffn(rows, n["d"], n["fs"])
    return r_ops + s_ops, r_bytes + s_bytes


def mla_lane(arch: dict, live: int, k_keep: int,
             use_lop: bool = True) -> tuple[float, float]:
    """(int8 ops, bytes) of one lane's latent attention in one layer at
    ``live`` valid positions: the absorption q_nope·W_UKᵀ, the screen
    over the live rows' packed features, the kept rows read once (scores
    over all dl columns, values over the c part), W_UV; the lane's
    query and output rows."""
    n = dims(arch)
    h, dl, dc = n["h"], n["dl"], n["dc"]
    block = arch["lop_block"]
    exact = live
    if use_lop and live > k_keep * block:
        exact = (k_keep - 1) * block + (live - (live - 1) // block * block)
    ops = (2.0 * h * n["dn"] * dc + 2.0 * h * exact * (dl + dc)
           + 2.0 * h * dc * n["dv"])
    nbytes = (exact * (dl + cost.F32)
              + h * (n["dn"] + n["dv"]) * cost.F32)
    if use_lop:
        ops += 2.0 * h * live * dl
        nbytes += live * dl / 2
    return ops, nbytes


def mla_step(arch: dict) -> tuple[float, float]:
    """(int8 ops, bytes) one decode step's latent attention needs in one
    layer whatever its lanes: the W_UK and W_UV codes."""
    n = dims(arch)
    return 0.0, 2.0 * n["h"] * n["dn"] * n["dc"] / 4


def decode_attention(arch: dict, live: int, k_keep: int,
                     use_lop: bool = True) -> tuple[float, float]:
    """(int8 ops, bytes) of one lane's decode-attention launches in one
    layer: the screen (where it can prune) and the kept latent rows,
    read once for scores and values."""
    n = dims(arch)
    h, dl, dc = n["h"], n["dl"], n["dc"]
    block = arch["lop_block"]
    exact = live
    ops = 0.0
    nbytes = h * dl + h * dc * cost.F32               # q int8, out f32
    if use_lop and live > k_keep * block:
        exact = (k_keep - 1) * block + (live - (live - 1) // block * block)
        ops += 2.0 * h * live * dl
        nbytes += live * dl / 2
    ops += 2.0 * h * exact * (dl + dc)
    nbytes += exact * (dl + cost.F32)
    return ops, nbytes


def decode_attention_calls(arch: dict, live: int, k_keep: int,
                           use_lop: bool = True) -> list:
    return [decode_attention(arch, live, k_keep, use_lop)
            + (arch["n_layers"],)]


def decode_projection_calls(arch: dict, rows: int) -> dict:
    """{kernel: [(ops, bytes) of each call in one MoE layer]}: the
    qlinear kernel runs q ‖ kv_a, W_UK, W_UV and o_proj; the ffn kernel
    the routed experts (all E buckets of ``rows`` rows are run) and the
    shared experts."""
    n = dims(arch)
    h = n["h"]
    q_kva = cost.qlinear(rows, n["d"], n["q"] + n["dl"])
    uk = cost.qlinear(rows, n["dn"], n["dc"])
    uv = cost.qlinear(rows, n["dc"], n["dv"])
    return {"qlinear": [q_kva, (h * uk[0], h * uk[1]),
                        (h * uv[0], h * uv[1]),
                        cost.qlinear(rows, n["o"], n["d"])],
            "ffn": [routed_experts(arch, rows),
                    cost.ffn(rows, n["d"], n["fs"])]}


def decode_token_ops(arch: dict, live: int, k_keep: int,
                     use_lop: bool = True) -> float:
    """Operations one decoded token needs: 2 per ternary weight it
    passes (projections, absorption, W_UV, its top_k experts, the shared
    ones, layer 0's FFN), 2 per LM-head weight, and the attention of
    every layer at ``live``."""
    n = dims(arch)
    att = n["d"] * (n["q"] + n["dl"]) + n["o"] * n["d"] + n["h"] * (
        n["dn"] * n["dc"] + n["dc"] * n["dv"])
    moe = 3 * n["d"] * (n["k"] * n["fe"] + n["fs"])
    ffn = 3 * n["d"] * n["f"]
    a_ops, _ = decode_attention(arch, live, k_keep, use_lop)
    return (2.0 * (arch["n_layers"] * att + n["moe"] * moe
                   + n["dense"] * ffn)
            + 2.0 * n["d"] * n["v"] + arch["n_layers"] * a_ops)
