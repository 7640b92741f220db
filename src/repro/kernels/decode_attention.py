"""Fused batched decode attention — the whole step in two Pallas launches.

This is the decode hot path of the paper (§III): each ``pallas_call``
has a grid spanning (batch × kv-head) with a sequential streaming axis,
replacing the previous per-head small-kernel dispatch (``lop_screen`` +
jnp block top-K + ``sparse_decode`` under a triple ``vmap``) that TeLLMe
v2 / HSA-style analyses identify as the utilization killer on edge
accelerators (PAPERS.md). Per (b, kv-head) lane the LOP path runs three
phases (DESIGN.md §Fused-decode-kernel):

  screen   launch 1, steps ``j < NB``: the packed 4-bit (sgn‖LO)
           feature cache streams block by block; the two nibble planes
           expand to pot-int8 in VMEM separately, and two int8 MXU dots
           against pot(q)'s even and odd columns (split once, outside
           the kernel) sum to the surrogate scores; invalid tokens mask
           to INT32_MIN, and the per-block maxima land in VMEM scratch
           (fully-masked blocks score −inf so they can never be picked).
           The planes are never interleaved back into [block, d]:
           Mosaic lowers that lane interleave to thousands of relayout
           ops (sublane shuffles, gathers) a block, 17× the ops of the
           whole two-dot step.
  select   launch 1, step ``j == NB − 1``: the comparison-free
           bucketized top-K (the ASIC's histogram + prefix-scan
           selector, mirroring :func:`repro.core.lop.comparison_free_topk`
           op for op) turns the block scores into an emission *rank* per
           block — no comparator tree, no sort. A few jnp ops turn the
           ranks into one block id per candidate.
  exact    launch 2, step ``c`` per candidate in rank order: the block
           ids ride in SMEM as scalar prefetch, and the BlockSpec index
           map fetches ONLY candidate c's int8 K/V block (plus scales)
           from HBM. The pipeline fetches candidate c+1 while c is on
           the MXU (the paper's head-level pipelining), then c folds
           into f32 online-softmax state (m/ℓ/acc scratch,
           output-stationary like the paper's OS dataflow). Un-selected
           blocks are never fetched — the LOP traffic win survives.

The exact phase fetches through the pipeline, not a hand-written DMA:
Mosaic refuses a DMA of a [block, d] slice whose lane dim d is not a
multiple of 128 (bitnet-3b has d = 100), while a BlockSpec block may
span the whole dim. A gated-off candidate repeats the previous block id,
so the pipeline fetches nothing for it.

The final step normalizes with an ``ℓ > 0`` guard, so a lane with
``new_len == 0`` (a retired slot-pool lane) emits exactly zero.

Scalar-prefetch contract
------------------------
``new_len`` int32 [B] (per-lane valid length, 0 = retired lane) and
``pos_offset`` int32 [1] (global token position of this cache shard —
the SP quota-sharded path passes ``rank · M_local``) ride in SMEM ahead
of the grid. They drive validity masking, the in-block live interval
[start, end) of each candidate, and nothing else, which is what lets one
compiled kernel serve every lane population and every SP shard. The
exact launch also prefetches the candidates' block ids and gates
(int32 [B·Hkv·n_cand] each), which address its K/V blocks.

Modes (all static):

  * ``use_lop=False``  — dense baseline: one launch streams every K/V
    block through the online-softmax phase (no screen, no gather).
  * ``shared_select``  — one candidate set per kv head (group max of
    the surrogate scores) instead of per q-head: K block gathers
    instead of G·K.
  * ``return_stats``   — also emit the raw (m, ℓ) softmax stats so the
    SP path can merge shards flash-decoding style without recomputing.

Interpret mode is the CPU test path; ``tests/test_chip_compile.py``
compiles both forms with Mosaic for a v5e at bitnet-3b widths. Every op
in :func:`repro.core.lop.comparison_free_rank` keeps 2-D (sublane,
lane) shape — the histogram runs as per-bucket lane-reductions over
[R, M] broadcast-compares and the index-order prefix sums as f32 MXU
dots against a triangular ones matrix — with ranks bitwise the flat-op
implementation it replaced.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.xla_metadata import set_xla_metadata

from repro.core.lop import DEFAULT_N_BUCKETS, comparison_free_rank, pot
from repro.kernels.lop_scores import plane_scores

NEG_INF = -1e30
INT32_MIN = jnp.iinfo(jnp.int32).min


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


# ---------------------------------------------------------------------------
# Shared online-softmax update
# ---------------------------------------------------------------------------

def _online_update(s, v_deq, rows, m_ref, l_ref, acc_ref):
    """Fold one [R, block] logit tile into the [rows] slice of the state."""
    m_prev = m_ref[rows, :]
    l_prev = l_ref[rows, :]
    m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, :1])
    l_ref[rows, :] = l_prev * alpha + jnp.sum(p, -1, keepdims=True)
    # HIGHEST: Mosaic's default f32 dot rounds its operands to bf16
    acc_ref[rows, :] = acc_ref[rows, :] * alpha[:, :1] + jnp.dot(
        p, v_deq, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    m_ref[rows, :] = m_new


def _flush(o_ref, m_out, l_out, m_ref, l_ref, acc_ref, return_stats):
    l = l_ref[:, :1]
    o_ref[0] = acc_ref[...] / jnp.where(l > 0, l, 1.0)
    if return_stats:
        m_out[0] = m_ref[:, :1]
        l_out[0] = l


# ---------------------------------------------------------------------------
# LOP launch 1: screen + select
# ---------------------------------------------------------------------------

def _lop_screen_kernel(nl_ref, po_ref, qe_ref, qo_ref, feat_ref, rank_ref,
                       blk_ref, *, nb, hkv, block, k_keep, window, n_buckets,
                       shared_select):
    """Grid (b·hkv, NB): screen every feature block, then rank them."""
    bh = pl.program_id(0)
    j = pl.program_id(1)
    nl = nl_ref[bh // hkv]
    po = po_ref[0]

    @pl.when(j == 0)
    def _init():
        blk_ref[...] = jnp.full_like(blk_ref, -jnp.inf)

    # ---- screen: surrogate block scores from the packed feature cache ----
    s = plane_scores(qe_ref[0], qo_ref[0], feat_ref[0])  # [G, block] int32
    tpos = po + j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    tvalid = tpos < nl
    if window:
        tvalid &= tpos >= nl - window
    s = jnp.where(tvalid, s, INT32_MIN)
    if shared_select:
        s = jnp.max(s, axis=0, keepdims=True)            # [1, block]
    score = jnp.max(s, -1, keepdims=True).astype(jnp.float32)
    # a block with no valid token must never be selectable
    any_valid = jnp.any(tvalid[:1])
    # lane j of the score row, written as a masked whole-row update:
    # Mosaic only stores lane slices at multiples of 128
    lane = jax.lax.broadcasted_iota(jnp.int32, blk_ref.shape, 1)
    blk_ref[...] = jnp.where(lane == j, jnp.where(any_valid, score, -jnp.inf),
                             blk_ref[...])

    # ---- select: comparison-free top-K → emission ranks (once). The rank
    # computation is THE shared implementation from core.lop (also behind
    # the jnp oracle's comparison_free_topk), running here inside the
    # kernel body — kernel and oracle cannot drift apart. ----
    @pl.when(j == nb - 1)
    def _select():
        rank_ref[0] = comparison_free_rank(blk_ref[...], k_keep, n_buckets)


def _candidates(rank, k_keep: int, nb: int):
    """Emission ranks [BH, R, NBp] → (block id, gate) int32, flat
    [BH·R·k_keep] in candidate order c = row·k_keep + rank.

    A gated-off candidate (fewer live blocks than k_keep) points at the
    block of the candidate before it, so the pipeline sees an unchanged
    block index and fetches nothing for it.
    """
    kc = jnp.arange(k_keep, dtype=jnp.int32)[:, None]
    hit = rank[:, :, None, :] == kc                      # [BH, R, K, NBp]
    cols = jnp.arange(rank.shape[-1], dtype=jnp.int32)
    gate = jnp.any(hit, -1).reshape(-1)
    ids = jnp.min(jnp.where(hit, cols, nb), -1).reshape(-1)
    pos = jnp.arange(ids.shape[0], dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(gate, pos, -1), axis=0)
    ids = jnp.where(last >= 0, ids[jnp.maximum(last, 0)], 0)
    return ids.astype(jnp.int32), gate.astype(jnp.int32)


# ---------------------------------------------------------------------------
# LOP launch 2: exact attention over the selected blocks
# ---------------------------------------------------------------------------

def _lop_exact_kernel(nl_ref, po_ref, id_ref, gate_ref, qi_ref, qs_ref,
                      k_ref, v_ref, ks_ref, vs_ref, o_ref, *rest, g, n_cand,
                      hkv, block, k_keep, window, softmax_scale,
                      shared_select, return_stats):
    """Grid (b·hkv, n_cand): fold candidate c's K/V block (fetched by the
    pipeline from the scalar-prefetched block id) into the softmax state."""
    if return_stats:
        m_out, l_out = rest[0], rest[1]
        rest = rest[2:]
    else:
        m_out = l_out = None
    m_ref, l_ref, acc_ref = rest

    bh = pl.program_id(0)
    c = pl.program_id(1)
    nl = nl_ref[bh // hkv]
    po = po_ref[0]
    cand = bh * n_cand + c

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(gate_ref[cand] != 0)
    def _attend():
        idx = id_ref[cand]
        kb = k_ref[0]                                    # [block, d]
        ksb = ks_ref[0]                                  # [block, 1]
        if shared_select:
            rows = slice(None)
            q = qi_ref[0]                                # [G, d]
            qs = qs_ref[0]                               # [G, 1]
        else:
            # query row c // k_keep, picked by a masked sum over the G
            # rows: Mosaic cannot load one int8 row at a dynamic sublane
            # offset
            r = c // k_keep
            rows = pl.ds(r, 1)
            pick = jax.lax.broadcasted_iota(jnp.int32, (g, 1), 0) == r
            q = jnp.sum(jnp.where(pick, qi_ref[0].astype(jnp.int32), 0), 0,
                        keepdims=True).astype(jnp.int8)  # [1, d]
            qs = jnp.sum(jnp.where(pick, qs_ref[0], 0.0), 0, keepdims=True)
        s = jax.lax.dot_general(
            q, kb, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.float32)
        s = s * qs * ksb.reshape(1, block) * softmax_scale
        # in-block live interval [start, end): suffix cut by the cache
        # length, prefix cut by the SWA window
        blk_start = po + idx * block
        end = jnp.clip(nl - blk_start, 0, block)
        tstart = jnp.clip(nl - window - blk_start, 0, block) if window else 0
        t = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((t >= tstart) & (t < end), s, NEG_INF)
        v_deq = v_ref[0].astype(jnp.float32) * vs_ref[0]
        _online_update(s, v_deq, rows, m_ref, l_ref, acc_ref)

    @pl.when(c == n_cand - 1)
    def _finish():
        _flush(o_ref, m_out, l_out, m_ref, l_ref, acc_ref, return_stats)


# ---------------------------------------------------------------------------
# Fused dense kernel body (no-LOP baseline on the same grid layout)
# ---------------------------------------------------------------------------

def _fused_dense_kernel(nl_ref, po_ref, qi_ref, qs_ref, k_ref, v_ref,
                        ks_ref, vs_ref, o_ref, *rest, nb, hkv, block,
                        window, softmax_scale, return_stats):
    """Grid (b·hkv, NB): exact attention streamed over every K/V block."""
    if return_stats:
        m_out, l_out = rest[0], rest[1]
        rest = rest[2:]
    else:
        m_out = l_out = None
    m_ref, l_ref, acc_ref = rest

    bh = pl.program_id(0)
    j = pl.program_id(1)
    nl = nl_ref[bh // hkv]
    po = po_ref[0]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    tpos0 = po + j * block + jax.lax.broadcasted_iota(jnp.int32,
                                                      (1, block), 1)
    tvalid0 = tpos0 < nl
    if window:
        tvalid0 &= tpos0 >= nl - window

    @pl.when(jnp.any(tvalid0))
    def _tile():
        s = jax.lax.dot_general(
            qi_ref[0], k_ref[0], dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.float32)
        s = s * qs_ref[0] * ks_ref[0].reshape(1, block) * softmax_scale
        s = jnp.where(tvalid0, s, NEG_INF)
        v_deq = v_ref[0].astype(jnp.float32) * vs_ref[0]
        _online_update(s, v_deq, slice(None), m_ref, l_ref, acc_ref)

    @pl.when(j == nb - 1)
    def _finish():
        _flush(o_ref, m_out, l_out, m_ref, l_ref, acc_ref, return_stats)


# ---------------------------------------------------------------------------
# Entry
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _phase(name: str):
    """Name the ops of one LOP phase ``name``: in their op names
    (``jax.named_scope``) and, as the frontend attribute ``scope``, in
    the instruction text a TPU profiler trace gives each device op.
    Pallas overwrites a launch's frontend attributes with its kernel
    metadata, so a launch takes the yielded mark as ``metadata=``."""
    with jax.named_scope(name), set_xla_metadata(scope=name):
        yield {"scope": name}


@functools.partial(jax.jit, static_argnames=(
    "hkv", "block", "k_keep", "window", "softmax_scale", "use_lop",
    "shared_select", "return_stats", "n_buckets", "n_slots", "interpret"))
def fused_decode_attention(qi, qsc, k_cache, v_cache, k_scale, v_scale,
                           feat, new_len, pos_off, *, hkv: int, block: int,
                           k_keep: int, window: int, softmax_scale: float,
                           use_lop: bool = True, shared_select: bool = False,
                           return_stats: bool = False,
                           n_buckets: int = DEFAULT_N_BUCKETS,
                           n_slots: int = 2,
                           interpret: bool = False):
    """One fused decode-attention step over every (batch, kv-head) lane.

    qi        int8   [BH, G, d]    new-token queries (BH = B·Hkv, grouped)
    qsc       f32    [BH, G, 1]    per-head absmax query scales
    k/v_cache int8   [BH, M, d]    exact caches (HBM-resident; only the
                                   selected candidate blocks are fetched)
    k/v_scale f32    [BH, M, 1]    per-token absmax scales
    feat      uint8  [BH, M, d/2]  packed (sgn‖LO) feature cache
    new_len   int32  [B]           valid tokens per lane (0 = retired slot)
    pos_off   int32  [1]           global position of cache row 0 (SP shard)
    n_slots   candidate K/V buffers in VMEM: 2 = double buffering, the
              default; 1 fetches each block after the previous fold.
              Mosaic pipelines no deeper. Bitwise either way.
    → f32 [BH, G, d]; with ``return_stats`` also (m, ℓ) f32 [BH, G, 1].
    """
    bhg, g, d = qi.shape
    m = k_cache.shape[1]
    assert m % block == 0, (m, block)
    assert n_slots in (1, 2), n_slots
    nb = m // block
    nbp = _round_up(nb, 128)                 # lane-padded score scratch
    g_sel = 1 if shared_select else g

    # index maps take (grid indices, *scalar-prefetch refs)
    lane = lambda bh, j, *_: (bh, 0, 0)
    outs = [jax.ShapeDtypeStruct((bhg, g, d), jnp.float32)]
    out_specs = [pl.BlockSpec((1, g, d), lane)]
    if return_stats:
        outs += [jax.ShapeDtypeStruct((bhg, g, 1), jnp.float32)] * 2
        out_specs += [pl.BlockSpec((1, g, 1), lane)] * 2
    softmax_scratch = [
        pltpu.VMEM((g, 128), jnp.float32),       # running max
        pltpu.VMEM((g, 128), jnp.float32),       # running sum-exp
        pltpu.VMEM((g, d), jnp.float32),         # output accumulator
    ]

    if not use_lop:
        block_j = lambda bh, j, *_: (bh, j, 0)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bhg, nb),
            in_specs=[
                pl.BlockSpec((1, g, d), lane),
                pl.BlockSpec((1, g, 1), lane),
                pl.BlockSpec((1, block, d), block_j),
                pl.BlockSpec((1, block, d), block_j),
                pl.BlockSpec((1, block, 1), block_j),
                pl.BlockSpec((1, block, 1), block_j),
            ],
            out_specs=out_specs if return_stats else out_specs[0],
            scratch_shapes=softmax_scratch,
        )
        out = pl.pallas_call(
            functools.partial(_fused_dense_kernel, nb=nb, hkv=hkv,
                              block=block, window=window,
                              softmax_scale=softmax_scale,
                              return_stats=return_stats),
            grid_spec=grid_spec,
            out_shape=outs if return_stats else outs[0],
            interpret=interpret,
        )(new_len, pos_off, qi, qsc, k_cache, v_cache, k_scale, v_scale)
        return out

    # A custom call takes its HLO instruction name from the innermost
    # scope around it: each launch names itself ``fused_decode_attention``
    # so its instruction keeps the name the dense launch has.
    with _phase("lop_screen") as mark:
        qp = pot(qi)
        half = pl.BlockSpec((1, g, d // 2), lane)
        rank = pl.pallas_call(
            functools.partial(_lop_screen_kernel, nb=nb, hkv=hkv,
                              block=block, k_keep=k_keep, window=window,
                              n_buckets=n_buckets,
                              shared_select=shared_select),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(bhg, nb),
                in_specs=[
                    half, half,
                    pl.BlockSpec((1, block, d // 2),
                                 lambda bh, j, *_: (bh, j, 0)),
                ],
                out_specs=pl.BlockSpec((1, g_sel, nbp), lane),
                scratch_shapes=[pltpu.VMEM((g_sel, nbp), jnp.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct((bhg, g_sel, nbp), jnp.int32),
            interpret=interpret,
            name="fused_decode_attention", metadata=mark,
        )(new_len, pos_off, qp[..., 0::2], qp[..., 1::2], feat)
        ids, gate = _candidates(rank, k_keep, nb)

    # the pipeline fetches candidate c's block by the prefetched id; with
    # two buffers the next fetch is in flight behind the MXU work of the
    # current candidate — the head-level pipelining the paper overlaps in
    # silicon. The buffer count only changes WHEN a block is fetched,
    # never which blocks fold or in what order, so n_slots = 1 is bitwise
    # n_slots = 2 (DESIGN.md §Autotuning).
    n_cand = k_keep * g_sel
    cand = lambda bh, c, nl, po, ids_, gate_: (bh, ids_[bh * n_cand + c], 0)
    buffered = pl.Buffered(n_slots)
    with _phase("lop_exact") as mark:
        return pl.pallas_call(
            functools.partial(_lop_exact_kernel, g=g, n_cand=n_cand,
                              hkv=hkv, block=block, k_keep=k_keep,
                              window=window, softmax_scale=softmax_scale,
                              shared_select=shared_select,
                              return_stats=return_stats),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(bhg, n_cand),
                in_specs=[
                    pl.BlockSpec((1, g, d), lane),
                    pl.BlockSpec((1, g, 1), lane),
                    pl.BlockSpec((1, block, d), cand,
                                 pipeline_mode=buffered),
                    pl.BlockSpec((1, block, d), cand,
                                 pipeline_mode=buffered),
                    pl.BlockSpec((1, block, 1), cand,
                                 pipeline_mode=buffered),
                    pl.BlockSpec((1, block, 1), cand,
                                 pipeline_mode=buffered),
                ],
                out_specs=out_specs if return_stats else out_specs[0],
                scratch_shapes=softmax_scratch,
            ),
            out_shape=outs if return_stats else outs[0],
            interpret=interpret,
            name="fused_decode_attention", metadata=mark,
        )(new_len, pos_off, ids, gate, qi, qsc, k_cache, v_cache, k_scale,
          v_scale)
