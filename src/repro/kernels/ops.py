"""Public jit'd wrappers around the Pallas kernels.

Dispatch policy
---------------
Every op has two interchangeable implementations with identical semantics:

  * ``pallas``  — the TPU kernel, compiled by Mosaic on a TPU. Elsewhere it
    runs with ``interpret=True``, the kernel body executing in Python:
    the CPU test path. ``tests/test_chip_compile.py`` compiles the
    served kernels with Mosaic for a described v5e, which catches what
    interpret mode accepts and Mosaic refuses.
  * ``ref``     — the pure-jnp oracle in :mod:`repro.kernels.ref`. XLA lowers
    it to the same MXU int8 dots on TPU; it is also what the full-size
    dry-run traces (interpret-mode Pallas unrolls its grid at trace time,
    which would explode the HLO for production shapes).

``impl="auto"`` resolves to ``pallas`` on TPU and ``ref`` elsewhere, so the
same model code runs in tests (small shapes, interpret kernels), in the
dry-run (full shapes, ref path), and on real hardware (kernels).

All wrappers pad to the kernel block sizes and slice back.

Block shapes come from a three-step precedence chain
(:mod:`repro.kernels.autotune`, DESIGN.md §Autotuning): an active
``autotune.override`` context, then the swept ``TUNE_kernels.json``
table keyed by backend config and workload shape, then the hardcoded
defaults below — so with no table on disk every dispatch is bitwise the
pre-autotune behavior, and every swept knob is a pure tiling choice
pinned against the same oracles.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.core.lop import pot
from repro.core.quantization import rmsnorm as rmsnorm_ref
from repro.core.ternary import TernaryWeight
from repro.kernels import autotune as _tune
from repro.kernels import decode_attention as _dec
from repro.kernels import int8_attention as _attn
from repro.kernels import lop_scores as _lop
from repro.kernels import prefill_attention as _pf
from repro.kernels import qlinear as _ql
from repro.kernels import ref as _ref
from repro.kernels import rmsnorm as _rms
from repro.kernels import ternary_matmul as _tmm


def _resolve(impl: str) -> str:
    if impl != "auto":
        return impl
    env = os.environ.get("REPRO_KERNEL_IMPL")
    if env:
        return env
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _lead_rows(shape) -> int:
    """Row count after flattening leading dims (static Python ints)."""
    rows = 1
    for s in shape:
        rows *= int(s)
    return rows


def _pad_to(x: jax.Array, mult: int, axis: int):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


# ---------------------------------------------------------------------------
# Row-invariant ops on fixed row tiles
#
# XLA compiles a reduction or matmul per operand shape, and on a TPU v5e
# neither is row-invariant: bitnet-3b's layer-0 RMSNorm rows differed
# between a 300-row whole-prompt call and a 128-row prefill chunk, and
# the LM head's logits between 1-row and 4-row calls. The serving path
# needs a row's value to depend on that row alone — a whole-prompt
# prefill, a prefill chunk and a decode step of any lane count must
# agree bit for bit, or a pool lane's greedy tokens drift from the
# batch-1 reference — so these ops run one fixed program per
# ``ROW_TILE``-row tile, whatever the number of rows.
# ---------------------------------------------------------------------------

ROW_TILE = 8              # one f32 sublane tile


def map_row_tiles(fn, x: jax.Array) -> jax.Array:
    """``fn`` on zero-padded [ROW_TILE, d] tiles of x [..., d], one tile
    at a time (one compiled body) → [..., n_out]."""
    lead, d = x.shape[:-1], x.shape[-1]
    xp, n = _pad_to(x.reshape(-1, d), ROW_TILE, 0)
    y = jax.lax.map(fn, xp.reshape(-1, ROW_TILE, d))
    return y.reshape(xp.shape[0], -1)[:n].reshape(*lead, -1)


def rmsnorm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6, *,
            impl: str = "auto") -> jax.Array:
    """RMSNorm over the last axis of f32 x [..., d] with γ [d]; under
    ``pallas`` a Pallas grid over ``ROW_TILE``-row tiles."""
    if _resolve(impl) == "ref":
        return rmsnorm_ref(x, gamma, eps)
    d = x.shape[-1]
    xp, n = _pad_to(x.reshape(-1, d).astype(jnp.float32), ROW_TILE, 0)
    out = _rms.rmsnorm_rows(xp, gamma.astype(jnp.float32).reshape(1, d),
                            eps=eps, rows=ROW_TILE, interpret=_interpret())
    return out[:n].reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# TINT: packed-ternary × int8 GEMM
# ---------------------------------------------------------------------------

def ternary_matmul(x: jax.Array, tw: TernaryWeight, *,
                   impl: str = "auto") -> jax.Array:
    """int8 activations [..., k] × packed ternary weight → int32 [..., n].

    Output is the raw integer accumulator; the caller applies the absmax-
    barrier dequantization (one multiply by activation-scale × γ).
    """
    k, n = tw.shape
    lead = x.shape[:-1]
    x2 = x.reshape(-1, k)
    # log-and-sweep (DESIGN.md §Autotuning): shapes are static at trace
    # time, so each distinct dispatch shape is observed once per compile
    _tune.observe("ternary_matmul", {"m": x2.shape[0], "k": k, "n": n})
    if _resolve(impl) == "ref":
        out = _ref.ternary_matmul_ref(x2, tw.packed, k)
        return out.reshape(*lead, n)

    tuned = _tune.lookup("ternary_matmul",
                         {"m": x2.shape[0], "k": k, "n": n})
    bm = tuned.get("bm", min(_tmm.DEFAULT_BM, max(8, x2.shape[0])))
    bk = tuned.get("bk", min(_tmm.DEFAULT_BK, k))
    bn = tuned.get("bn", min(_tmm.DEFAULT_BN, n))
    xp, m0 = _pad_to(x2, bm, 0)
    assert k % bk == 0 and n % bn == 0, (k, n, bk, bn)
    out = _tmm.ternary_matmul(xp, tw.packed, k, bm=bm, bk=bk, bn=bn,
                              interpret=_interpret())
    return out[:m0].reshape(*lead, n)


# ---------------------------------------------------------------------------
# Fused TINT projections — THE linear entry points (DESIGN.md
# §TINT-projection-fusion): the absmax barrier, the packed-ternary GEMM
# and the dequant/bias/activation epilogue run as ONE dispatch.
# ---------------------------------------------------------------------------

def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _col_scale(scale: jax.Array, n: int) -> jax.Array:
    """Per-node γ (scalar or per-column row) → per-column f32 row [.., 1, n]."""
    return jnp.broadcast_to(scale.astype(jnp.float32),
                            scale.shape[:-2] + (1, n))


def qlinear_fused(x: jax.Array, packed: jax.Array, scale: jax.Array,
                  bias: jax.Array | None = None, *, act: str | None = None,
                  impl: str = "auto") -> jax.Array:
    """f32/bf16 activations [..., k] × packed ternary [k//4, n] → f32 [..., n].

    One dispatch replaces the quantize → ``ternary_matmul`` → dequant
    chain: the absmax row-quantize runs in VMEM inside the same kernel
    (the barrier), the epilogue fuses dequant by (x-scale · γ), bias and
    the optional activation. A 3-D ``packed`` [E, k//4, n] with x
    [E, C, k] runs the grouped-expert form — expert is a grid axis of
    the same launch, not a vmap of launches. ``scale`` is the node's γ:
    scalar [.., 1, 1] or per-column row [.., 1, n] (fused QKV).
    """
    expert = packed.ndim == 3
    k = packed.shape[-2] * 4
    n = packed.shape[-1]
    scale_row = _col_scale(scale, n)
    _tune.observe("qlinear", {"e": x.shape[0] if expert else 1,
                              "m": (x.shape[1] if expert
                                    else _lead_rows(x.shape[:-1])),
                              "k": k, "n": n})
    if _resolve(impl) == "ref":
        return _ref.qlinear_ref(x, packed, scale_row, bias, act=act)

    if expert:
        assert x.ndim == 3, x.shape
        x3, p3, s3 = x.astype(jnp.float32), packed, scale_row
        b3 = None if bias is None else bias.reshape(bias.shape[0], 1, n)
    else:
        x3 = x.reshape(-1, k).astype(jnp.float32)[None]
        p3, s3 = packed[None], scale_row[None]
        b3 = None if bias is None else bias.reshape(1, 1, n)
    m0 = x3.shape[1]
    tuned = _tune.lookup("qlinear", {"e": x3.shape[0], "m": m0,
                                     "k": k, "n": n})
    bm = tuned.get("bm", min(_tmm.DEFAULT_BM, _round_up(max(m0, 1), 8)))
    bn = tuned.get("bn") or _tune.pick_block(n, k // 4)
    pad = (-m0) % bm
    if pad:
        x3 = jnp.pad(x3, ((0, 0), (0, pad), (0, 0)))
    out = _ql.fused_qlinear(x3, p3, s3, b3, bm=bm, bn=bn,
                            bkq=tuned.get("bkq", 0),
                            eg=tuned.get("eg", 1),
                            act=act, interpret=_interpret())[:, :m0]
    if expert:
        return out
    return out.reshape(*x.shape[:-1], n)


def ffn_fused(x: jax.Array, gu_packed: jax.Array, gu_scale: jax.Array,
              down_packed: jax.Array, down_scale: jax.Array, *,
              gated: bool, act: str, impl: str = "auto") -> jax.Array:
    """The whole FFN — act(x·Wg)·(x·Wu) → absmax barrier → ·Wd — as ONE
    dispatch. x [..., d]; gu_packed [(E,) d//4, 2f] (gate ‖ up columns;
    [(E,) d//4, f] ungated); down_packed [(E,) f//4, d_out]. A leading
    expert dim with x [E, C, d] runs every expert of a MoE layer in the
    same launch (expert = third grid axis). → f32 [..., d_out].
    """
    expert = gu_packed.ndim == 3
    k = gu_packed.shape[-2] * 4
    f = down_packed.shape[-2] * 4
    d_out = down_packed.shape[-1]
    gu_row = _col_scale(gu_scale, gu_packed.shape[-1])
    down_row = _col_scale(down_scale, d_out)
    _tune.observe("ffn", {"e": x.shape[0] if expert else 1,
                          "m": (x.shape[1] if expert
                                else _lead_rows(x.shape[:-1])),
                          "k": k, "f": f, "n": d_out})
    if _resolve(impl) == "ref":
        return _ref.ffn_fused_ref(x, gu_packed, gu_row, down_packed,
                                  down_row, gated=gated, act=act)

    if expert:
        assert x.ndim == 3, x.shape
        x3, gu3, gs3 = x.astype(jnp.float32), gu_packed, gu_row
        d3, ds3 = down_packed, down_row
    else:
        x3 = x.reshape(-1, k).astype(jnp.float32)[None]
        gu3, gs3 = gu_packed[None], gu_row[None]
        d3, ds3 = down_packed[None], down_row[None]
    m0 = x3.shape[1]
    tuned = _tune.lookup("ffn", {"e": x3.shape[0], "m": m0, "k": k,
                                 "f": f, "n": d_out})
    bm = tuned.get("bm", min(_tmm.DEFAULT_BM, _round_up(max(m0, 1), 8)))
    # gated, gate ‖ up share one 2f-wide array: f is not its whole dim
    bf = tuned.get("bf") or _tune.pick_block(f, None if gated else k // 4)
    bn = tuned.get("bn") or _tune.pick_block(d_out, f // 4)
    pad = (-m0) % bm
    if pad:
        x3 = jnp.pad(x3, ((0, 0), (0, pad), (0, 0)))
    out = _ql.fused_ffn(x3, gu3, gs3, d3, ds3, bm=bm, bf=bf, bn=bn,
                        bkq=tuned.get("bkq", 0), act=act, gated=gated,
                        interpret=_interpret())[:, :m0]
    if expert:
        return out
    return out.reshape(*x.shape[:-1], d_out)


# ---------------------------------------------------------------------------
# LOP screen: surrogate scores from the packed feature cache
# ---------------------------------------------------------------------------

def lop_screen(q: jax.Array, feat_packed: jax.Array, *,
               impl: str = "auto") -> jax.Array:
    """int8 queries [..., d] × packed (sgn‖LO) cache [m, d//2] → int32 [..., m].

    Applies pot() rounding to q internally (the cache is already rounded).
    """
    d = q.shape[-1]
    m = feat_packed.shape[0]
    lead = q.shape[:-1]
    qp = pot(q).reshape(-1, d)
    if _resolve(impl) == "ref":
        out = _ref.lop_scores_ref(qp, feat_packed)
        return out.reshape(*lead, m)

    bq = min(_lop.DEFAULT_BQ, max(8, qp.shape[0]))
    bm = min(_lop.DEFAULT_BM, m)
    qpp, g0 = _pad_to(qp, bq, 0)
    assert m % bm == 0, (m, bm)
    # the kernel scores q's even and odd columns against the low and high
    # nibble planes (lop_scores.plane_scores)
    out = _lop.lop_scores_kernel(qpp[:, 0::2], qpp[:, 1::2], feat_packed,
                                 bq=bq, bm=bm, interpret=_interpret())
    return out[:g0].reshape(*lead, m)


# ---------------------------------------------------------------------------
# Int8 flash attention (prefill) and LOP block-sparse decode
# ---------------------------------------------------------------------------

def flash_prefill(q, k, v, q_scale, k_scale, v_scale, *,
                  softmax_scale: float, causal: bool = True, window: int = 0,
                  impl: str = "auto") -> jax.Array:
    """Single-head int8 flash attention; see kernel docstring for shapes."""
    if _resolve(impl) == "ref":
        return _ref.flash_prefill_ref(q, k, v, q_scale, k_scale, v_scale,
                                      softmax_scale=softmax_scale,
                                      causal=causal, window=window)
    s = q.shape[0]
    bq = min(_attn.DEFAULT_BQ, s)
    bk = min(_attn.DEFAULT_BK, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    return _attn.int8_flash_prefill(q, k, v, q_scale, k_scale, v_scale,
                                    softmax_scale=softmax_scale,
                                    causal=causal, window=window, bq=bq,
                                    bk=bk, interpret=_interpret())


def sparse_decode(q, k_cache, v_cache, q_scale, k_scale, v_scale,
                  block_idx, gate_tokens, *, block: int,
                  softmax_scale: float, impl: str = "auto") -> jax.Array:
    """Single-kv-head LOP-sparse decode micro-kernel.

    Kept as a standalone building block (microbenchmarks, kernel tests,
    the legacy-dispatch baseline in benchmarks/fig8_lop.py); the serving
    decode path dispatches through :func:`decode_attention` instead.
    """
    if _resolve(impl) == "ref":
        return _ref.sparse_decode_attention_ref(
            q, k_cache, v_cache, q_scale, k_scale, v_scale, block_idx,
            gate_tokens, block=block, softmax_scale=softmax_scale)
    return _attn.sparse_decode_attention(
        q, k_cache, v_cache, q_scale, k_scale, v_scale, block_idx,
        gate_tokens, block=block, softmax_scale=softmax_scale,
        interpret=_interpret())


# ---------------------------------------------------------------------------
# Fused batched prefill attention — THE prefill entry point
# ---------------------------------------------------------------------------

def prefill_attention(qi, qsc, k_cache, v_cache, k_scale, v_scale, kv_len, *,
                      q_offset=None, causal: bool = True, window: int = 0,
                      softmax_scale: float | None = None,
                      int8_logits: bool = False, impl: str = "auto"):
    """Single entry for every prefill-attention flavour (DESIGN.md
    §Chunked-prefill): whole-prompt prefill, chunked prefill, encoder
    self-attention (``causal=False``) and decoder cross-attention all
    route through this one op, so the chunked scheduler and the lockstep
    reference compute bit-identical rows under either dispatch arm.

    qi        int8  [B, H, C, dh]   chunk (or whole-prompt) queries
    qsc       f32   [B, H, C]       per-token-head absmax query scales
    k/v_cache int8  [B, Hkv, M, dh] caches with K/V written at [0, kv_len)
    k/v_scale f32   [B, Hkv, M]     per-token absmax scales
    kv_len    int32 [B]             valid cache tokens (incl. this chunk)
    q_offset       traced int32 scalar or None — global position of query
                   column 0 (chunked prefill passes its chunk start)
    → f32 [B, H, C, dh]

    ``impl="pallas"`` runs the fused kernel
    (:mod:`repro.kernels.prefill_attention`): one ``pallas_call`` whose
    grid spans (B·Hkv, kv-block stream) with f32 online-softmax carry in
    VMEM scratch. ``impl="ref"`` runs the jnp oracle, streamed over query
    chunks so dry-run traces stay memory-bounded. The wrapper pads M to
    the kernel block size; padded tokens sit beyond ``kv_len`` and fold
    as bitwise no-ops.
    """
    b, h, c, dh = qi.shape
    hkv, m = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    assert h == g * hkv, (h, hkv)
    if softmax_scale is None:
        softmax_scale = dh ** -0.5
    kv_len = kv_len.astype(jnp.int32)
    _tune.observe("prefill", {"bhg": b * hkv, "r": g * c, "d": dh,
                              "m": m, "chunk": c})

    if _resolve(impl) == "ref":
        return _ref.prefill_attention_ref(
            qi, qsc, k_cache, v_cache, k_scale, v_scale, kv_len,
            0 if q_offset is None else q_offset, causal=causal,
            window=window, softmax_scale=softmax_scale,
            int8_logits=int8_logits)

    tuned = _tune.lookup("prefill", {"bhg": b * hkv, "r": g * c, "d": dh,
                                     "m": m, "chunk": c})
    bk = tuned.get("block", min(_pf.DEFAULT_BK, m))
    pad = (-m) % bk
    if pad:
        widths = [(0, 0), (0, 0), (0, pad)]
        k_cache = jnp.pad(k_cache, widths + [(0, 0)])
        v_cache = jnp.pad(v_cache, widths + [(0, 0)])
        k_scale = jnp.pad(k_scale, widths)
        v_scale = jnp.pad(v_scale, widths)
        m += pad

    # flatten (B, Hkv) → the kernel's batched lane axis; rows g-major
    bh = b * hkv
    qig = qi.reshape(b, hkv, g, c, dh).reshape(bh, g * c, dh)
    qsg = qsc.reshape(b, hkv, g, c).reshape(bh, g * c, 1)
    po = jnp.full((1,), 0 if q_offset is None else q_offset, jnp.int32)
    out = _pf.fused_prefill_attention(
        qig, qsg, k_cache.reshape(bh, m, dh), v_cache.reshape(bh, m, dh),
        k_scale.reshape(bh, m, 1), v_scale.reshape(bh, m, 1), kv_len, po,
        hkv=hkv, chunk=c, block=bk, bq=tuned.get("bq", 0), causal=causal,
        window=window, softmax_scale=softmax_scale,
        int8_logits=int8_logits, interpret=_interpret())
    return out.reshape(b, h, c, dh)


# ---------------------------------------------------------------------------
# Fused batched decode attention — THE decode entry point
# ---------------------------------------------------------------------------

def decode_attention(qi, qsc, k_cache, v_cache, k_scale, v_scale, feat,
                     new_len, *, block: int, k_keep: int, window: int = 0,
                     softmax_scale: float | None = None,
                     use_lop: bool = True, shared_select: bool = False,
                     pos_offset=None, return_stats: bool = False,
                     impl: str = "auto"):
    """Single entry for every decode-attention flavour (DESIGN.md
    §Fused-decode-kernel).

    Serves the dense baseline (``use_lop=False``), the LOP-sparse path,
    group-shared selection (``shared_select``) and the SP-sharded path
    (``pos_offset`` + ``return_stats``) from one call:

    qi        int8  [B, H, dh]     new-token queries
    qsc       f32   [B, H, 1]      per-head absmax query scales
    k/v_cache int8  [B, Hkv, M, dh]
    k/v_scale f32   [B, Hkv, M]
    feat      uint8 [B, Hkv, M, dh//2]  packed (sgn‖LO) feature cache
    new_len   int32 [B]            valid tokens per lane; 0 = retired
                                   slot-pool lane (emits exactly zero)
    pos_offset     traced int32 scalar or None — global token position of
                   cache row 0 (the SP quota-sharded path passes its
                   shard offset; must be a multiple of ``block``)
    return_stats   also return the unnormalized softmax stats (m, ℓ)
                   f32 [B, H, 1] for the flash-decoding shard merge

    → f32 [B, H, dh]  (or ``(out, m, ℓ)`` with ``return_stats``).

    ``impl="pallas"`` runs the fused kernel
    (:mod:`repro.kernels.decode_attention`): one ``pallas_call`` whose
    grid spans (B·Hkv, stream) — screen, comparison-free top-K, and
    DMA-gathered exact attention in a single launch. ``impl="ref"`` runs
    the jnp oracle, which XLA fuses well enough for the dry-run traces.
    """
    b, h, dh = qi.shape
    hkv, m = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    assert h == g * hkv, (h, hkv)
    assert m % block == 0, (m, block)
    if softmax_scale is None:
        softmax_scale = dh ** -0.5
    _tune.observe("decode", {"bhg": b * hkv, "g": g, "d": dh, "m": m,
                             "block": block, "k_keep": k_keep})

    if _resolve(impl) == "ref":
        return _ref.decode_attention_ref(
            qi, qsc, k_cache, v_cache, k_scale, v_scale, feat, new_len,
            block=block, k_keep=k_keep, window=window,
            softmax_scale=softmax_scale, use_lop=use_lop,
            shared_select=shared_select, pos_offset=pos_offset,
            return_stats=return_stats)

    # flatten (B, Hkv) → the kernel's batched lane axis
    bh = b * hkv
    qig = qi.reshape(b, hkv, g, dh).reshape(bh, g, dh)
    qsg = qsc.reshape(b, hkv, g, 1).reshape(bh, g, 1)
    kf = k_cache.reshape(bh, m, dh)
    vf = v_cache.reshape(bh, m, dh)
    ksf = k_scale.reshape(bh, m, 1)
    vsf = v_scale.reshape(bh, m, 1)
    featf = feat.reshape(bh, m, dh // 2)
    po = jnp.full((1,), 0 if pos_offset is None else pos_offset, jnp.int32)
    tuned = _tune.lookup("decode", {"bhg": bh, "g": g, "d": dh, "m": m,
                                    "block": block, "k_keep": k_keep})
    out = _dec.fused_decode_attention(
        qig, qsg, kf, vf, ksf, vsf, featf, new_len.astype(jnp.int32), po,
        hkv=hkv, block=block, k_keep=k_keep, window=window,
        softmax_scale=softmax_scale, use_lop=use_lop,
        shared_select=shared_select, return_stats=return_stats,
        n_slots=tuned.get("n_slots", 2), interpret=_interpret())
    if return_stats:
        o, ms, ls = out
        return (o.reshape(b, h, dh), ms.reshape(b, h, 1),
                ls.reshape(b, h, 1))
    return out.reshape(b, h, dh)
