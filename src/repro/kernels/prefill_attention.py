"""Fused batched prefill attention — causal int8 flash over token chunks.

The prefill counterpart of :mod:`repro.kernels.decode_attention`: ONE
``pallas_call`` whose grid spans (batch × kv-head) lanes with a sequential
KV-block streaming axis, computing causal (or cross) int8 attention for a
fixed-size *chunk* of queries against the capacity-padded cache — the
quantized K/V the engine wrote at [0, kv_len). This is what lets the
scheduler interleave one prefill chunk with the running decode batch per
``serve_step`` instead of stalling every lane behind a whole prompt
(DESIGN.md §Chunked-prefill): the chunk shape is FIXED, so prefill
compiles collapse from one-per-pow2-bucket to one shape, and TeLLMe-v2
style prefill acceleration rides the same ``ops.*`` interface the decode
kernel standardized.

Per (b, kv-head) lane the streaming axis walks every KV block ``j``:

  gate     blocks entirely beyond the lane's valid length, or entirely
           above the causal diagonal of the chunk, are skipped (their
           fold would be a bitwise no-op anyway — see below).
  logits   one MXU dot per block: either integer-domain
           (int8×int8→int32, BoothFlex-faithful, ``int8_logits``) or the
           dequantize-K-then-f32 form — both scaled by the per-token
           absmax scales of the quantization barrier.
  mask     query row r = g·chunk + t sits at global position
           ``q_off + t``; tokens outside [max(0, qpos-window+1), qpos]
           or ≥ kv_len mask to −∞. Fully-masked rows are guarded: their
           probability tile is zeroed explicitly so the online-softmax
           state never absorbs exp(−∞ − −∞) = 1 garbage.
  fold     f32 online-softmax (m/ℓ/acc VMEM scratch, output-stationary
           like the paper's OS dataflow); the final block normalizes
           with an ℓ > 0 guard so an empty lane emits exactly zero.

Chunk-carry exactness (the contract the scheduler relies on)
------------------------------------------------------------
A query row's fold sequence is independent of every other row in the
call: blocks it cannot see are either gated off or fully masked, and a
fully-masked fold is *bitwise* a no-op (max(m, −∞) = m, ℓ += 0, acc +=
0). Therefore running a prompt through this kernel in C-token chunks
(each attending the cache written so far, ``q_off`` = chunk start)
produces bit-identical rows to one whole-prompt call over the same
capacity-padded cache — no inter-chunk softmax state needs to leave the
kernel; the carry IS the cache plus ``(q_off, kv_len)``. The jnp oracle
(:func:`repro.kernels.ref.prefill_attention_ref`) holds the same
invariant, so token-exact chunked-vs-lockstep agreement survives both
``REPRO_KERNEL_IMPL`` arms.

Scalar-prefetch contract (mirrors the decode kernel)
----------------------------------------------------
``kv_len`` int32 [B] — tokens valid in each lane's cache *after* this
chunk's K/V were written (0 = nothing valid → zero output); ``q_off``
int32 [1] — global position of chunk row t = 0. They drive gating and
masking only; tensor operands are addressed by the grid alone.

Interpret mode is the CPU test path; ``tests/test_chip_compile.py``
compiles the kernel with Mosaic for a v5e at bitnet-3b widths. The R
query rows can be tiled over a third grid axis (``bq``, the
carried-forward ROADMAP.md item, now a sweepable knob of
DESIGN.md §Autotuning): each bq-row slab walks the same kv blocks the
untiled kernel walks, so any legal ``bq`` (a divisor of R that is a
multiple of the 8-row sublane tile) is bitwise ``bq = R`` while
capping resident VMEM at bq·(256 + d) f32 — what makes whole-prompt
32k-row calls compilable on real hardware.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

DEFAULT_BK = 128


def _fused_prefill_kernel(kvl_ref, qo_ref, qi_ref, qs_ref, k_ref, v_ref,
                          ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref, *,
                          nb, hkv, chunk, block, bq, causal, window,
                          softmax_scale, int8_logits):
    """Grid (b·hkv, query-row tile qt, kv-block j); j streams sequentially.

    The second axis tiles the R query rows in ``bq``-row slabs (the
    carried-forward third grid axis: R never has to fit VMEM whole). A
    row's fold sequence is unchanged by the tiling — the kv gate stays
    the whole-chunk one, so a slab walks exactly the blocks the untiled
    kernel walks and masked folds remain bitwise no-ops — hence any
    ``bq`` is bitwise ``bq = R``.
    """
    bh = pl.program_id(0)
    qt = pl.program_id(1)
    j = pl.program_id(2)
    kvl = kvl_ref[bh // hkv]
    qo = qo_ref[0]
    r = qi_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # gate: blocks beyond the valid cache, or entirely above the causal
    # diagonal of this chunk, contribute nothing
    run = j * block < kvl
    if causal:
        run = jnp.logical_and(run, j * block <= qo + chunk - 1)

    @pl.when(run)
    def _tile():
        k = k_ref[0]                                     # [block, d] int8
        ks = ks_ref[0]                                   # [block, 1] f32
        # both branches dequantize AFTER the dot (int8 products summed in
        # f32 are exact below 2²⁴), so int8_logits only picks the MXU
        # datapath — see prefill_attention_ref for the knife-edge this
        # avoids
        if int8_logits:
            s = jax.lax.dot_general(
                qi_ref[0], k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32).astype(jnp.float32)
        else:
            s = jax.lax.dot_general(
                qi_ref[0].astype(jnp.float32), k.astype(jnp.float32),
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        s = s * ks.reshape(1, block) * qs_ref[0] * softmax_scale

        kpos = j * block + jax.lax.broadcasted_iota(jnp.int32, (r, block), 1)
        mask = kpos < kvl
        if causal:
            # row qt·bq + i = g*chunk + t → in-chunk offset t → query pos
            t = jax.lax.rem(
                qt * bq +
                jax.lax.broadcasted_iota(jnp.int32, (r, block), 0), chunk)
            qpos = qo + t
            mask = jnp.logical_and(mask, kpos <= qpos)
            if window:
                mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)

        # online-softmax fold with an all-masked-row guard: rows whose
        # tile is fully −∞ while m is still −∞ must not absorb
        # exp(−∞ − −∞) = 1 per position
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        l_ref[...] = l_prev * alpha + jnp.sum(p, -1, keepdims=True)
        v_deq = v_ref[0].astype(jnp.float32) * vs_ref[0]
        # HIGHEST: Mosaic's default f32 dot rounds its operands to bf16
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jnp.dot(
            p, v_deq, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nb - 1)
    def _flush():
        l = l_ref[:, :1]
        o_ref[0] = acc_ref[...] / jnp.where(l > 0, l, 1.0)


@functools.partial(jax.jit, static_argnames=(
    "hkv", "chunk", "block", "bq", "causal", "window", "softmax_scale",
    "int8_logits", "interpret"))
def fused_prefill_attention(qi, qsc, k_cache, v_cache, k_scale, v_scale,
                            kv_len, pos_off, *, hkv: int, chunk: int,
                            block: int, bq: int = 0, causal: bool,
                            window: int, softmax_scale: float,
                            int8_logits: bool = False,
                            interpret: bool = False) -> jax.Array:
    """One fused prefill-chunk attention over every (batch, kv-head) lane.

    qi        int8  [BH, R, d]   chunk queries (BH = B·Hkv; R = G·chunk,
                                 rows g-major: row = g·chunk + t)
    qsc       f32   [BH, R, 1]   per-token-head absmax query scales
    k/v_cache int8  [BH, M, d]   capacity-padded caches (chunk K/V already
                                 written at [q_off, q_off + chunk))
    k/v_scale f32   [BH, M, 1]   per-token absmax scales
    kv_len    int32 [B]          valid tokens incl. this chunk (0 = none)
    pos_off   int32 [1]          global position of chunk row t = 0
    bq        query rows resident per grid step (0 → all R rows, the
              historical shape); any divisor of R is bitwise-equivalent
    → f32 [BH, R, d]
    """
    bhg, r, d = qi.shape
    assert r % chunk == 0, (r, chunk)
    m = k_cache.shape[1]
    assert m % block == 0, (m, block)
    nb = m // block
    if bq == 0:
        bq = r
    assert r % bq == 0, (r, bq)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bhg, r // bq, nb),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qt, j, kvl, qo: (bh, qt, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, qt, j, kvl, qo: (bh, qt, 0)),
            pl.BlockSpec((1, block, d),
                         lambda bh, qt, j, kvl, qo: (bh, j, 0)),
            pl.BlockSpec((1, block, d),
                         lambda bh, qt, j, kvl, qo: (bh, j, 0)),
            pl.BlockSpec((1, block, 1),
                         lambda bh, qt, j, kvl, qo: (bh, j, 0)),
            pl.BlockSpec((1, block, 1),
                         lambda bh, qt, j, kvl, qo: (bh, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d),
                               lambda bh, qt, j, kvl, qo: (bh, qt, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),  # running max (lanes equal)
            pltpu.VMEM((bq, 128), jnp.float32),  # running sum-exp
            pltpu.VMEM((bq, d), jnp.float32),    # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_fused_prefill_kernel, nb=nb, hkv=hkv, chunk=chunk,
                          block=block, bq=bq, causal=causal, window=window,
                          softmax_scale=softmax_scale,
                          int8_logits=int8_logits),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bhg, r, d), jnp.float32),
        interpret=interpret,
    )(kv_len, pos_off, qi, qsc, k_cache, v_cache, k_scale, v_scale)
