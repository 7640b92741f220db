"""Leading-One Prediction (LOP) predictive sparse attention — paper §III-A.

The surrogate score

    ŝ(q,k) = Σᵢ sgn(qᵢ)·sgn(kᵢ)·2^(LO(qᵢ)+LO(kᵢ)),   LO(x) = ⌊log₂|x|⌋

is *exactly* the dot product of power-of-two-rounded vectors
``pot(x) = sgn(x)·2^LO(|x|)`` — the key TPU-native observation: the ASIC's
barrel-shift ExpAdd array becomes an int8 MXU matmul against a 4-bit packed
feature cache (sgn‖LO per element, two per byte → the feature cache reads
half the bytes of the exact int8 keys).

Selection is *comparison-free* (paper's bucketized k-degree selector [6]):
scores are bucketized, a high-to-low prefix scan finds the cut bin where the
cumulative count first reaches K, and indices are emitted without any
pairwise comparator tree. We keep the paper's *block* granularity ("only
those candidate blocks are requested") so KV fetches stay contiguous and
TPU-aligned.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# LO field values 0..6 encode ⌊log₂|x|⌋ for |x| ∈ [1,127]; 7 encodes x == 0.
LO_ZERO = 7


def leading_one(x: jax.Array) -> jax.Array:
    """⌊log₂|x|⌋ for int8 magnitudes, exactly, without floats.

    |x| ∈ [1,127] → LO ∈ [0,6];  x == 0 → LO_ZERO (7).
    """
    v = jnp.abs(x.astype(jnp.int32))
    lo = ((v >= 2).astype(jnp.int32) + (v >= 4) + (v >= 8)
          + (v >= 16) + (v >= 32) + (v >= 64))
    return jnp.where(v == 0, LO_ZERO, lo).astype(jnp.int32)


def pot(x: jax.Array) -> jax.Array:
    """Power-of-two rounding: sgn(x)·2^LO(|x|) as int8 (0 stays 0, max ±64)."""
    lo = leading_one(x)
    mag = jnp.where(lo == LO_ZERO, 0, jnp.left_shift(1, jnp.minimum(lo, 6)))
    return (jnp.sign(x.astype(jnp.int32)) * mag).astype(jnp.int8)


def lop_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """Surrogate scores ŝ = pot(q)·pot(k)ᵀ in int32 (multiplier-free on the
    ASIC; an int8 MXU matmul here).  q: [..., d], k: [..., M, d] → [..., M]."""
    qp, kp = pot(q), pot(k)
    return jnp.einsum("...d,...md->...m", qp, kp,
                      preferred_element_type=jnp.int32)


# ---------------------------------------------------------------------------
# 4-bit (sgn‖LO) feature packing — the LOP feature cache
# ---------------------------------------------------------------------------

def lop_features(x: jax.Array) -> jax.Array:
    """Per-element 4-bit feature nibble: (sgn_bit << 3) | LO.  int8 storage of
    the nibble is the reference layout; `pack_features` halves it."""
    lo = leading_one(x)
    sgn = (x < 0).astype(jnp.int32)
    return ((sgn << 3) | lo).astype(jnp.uint8)


def features_to_pot(feat: jax.Array) -> jax.Array:
    """Decode nibbles back to pot() int8 values.

    Also the in-kernel decode of the LOP screen
    (``kernels.lop_scores.nibble_planes_to_pot``), which passes int32
    nibbles: Mosaic cannot shift int8 vectors."""
    lo = (feat & 0x7).astype(jnp.int32)
    sgn = ((feat >> 3) & 0x1).astype(jnp.int32)
    mag = jnp.where(lo == LO_ZERO, 0, jnp.left_shift(1, jnp.minimum(lo, 6)))
    return ((1 - 2 * sgn) * mag).astype(jnp.int8)


def pack_features(feat: jax.Array) -> jax.Array:
    """Pack nibble features [..., d] (d even) → uint8 [..., d//2]."""
    lo_nib = feat[..., 0::2]
    hi_nib = feat[..., 1::2]
    return (lo_nib | (hi_nib << 4)).astype(jnp.uint8)


def unpack_features(packed: jax.Array) -> jax.Array:
    """uint8 [..., d//2] → nibble features [..., d]."""
    lo_nib = packed & 0xF
    hi_nib = (packed >> 4) & 0xF
    return jnp.stack([lo_nib, hi_nib], axis=-1).reshape(
        packed.shape[:-1] + (packed.shape[-1] * 2,)).astype(jnp.uint8)


# ---------------------------------------------------------------------------
# Comparison-free top-K (bucketized histogram / prefix-scan selector)
# ---------------------------------------------------------------------------

# One bucket count shared by every selector instance: the oracle-side
# `serving/lop_select.select_blocks` and the in-kernel selector of
# `kernels/decode_attention` must bucketize identically to pick identical
# candidate sets.
DEFAULT_N_BUCKETS = 64


def _cumsum_lanes(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum along the LANE axis of a 2-D (sublane, lane)
    tile, expressed as one f32 MXU dot against an upper-triangular ones
    matrix — the Mosaic-friendly retile of ``jnp.cumsum(x, -1)``.

    ``cumsum[r, j] = Σ_{i ≤ j} x[r, i] = (x @ T)[r, j]`` with
    ``T[i, j] = (i ≤ j)``. Counts are integers far below 2²⁴, so the f32
    accumulation is exact and the result is bitwise the integer cumsum.
    """
    m = x.shape[-1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (m, m), 1)
    tri = (rows <= cols).astype(jnp.float32)
    out = jax.lax.dot(x.astype(jnp.float32), tri,
                      preferred_element_type=jnp.float32)
    return out.astype(jnp.int32)


def comparison_free_rank(s: jax.Array, k: int,
                         n_buckets: int = DEFAULT_N_BUCKETS) -> jax.Array:
    """Emission ranks of the bucketized selector: f32 [R, M] → int32 [R, M].

    THE single implementation of the comparison-free selection order —
    `comparison_free_topk` (jnp oracle side) and the fused decode kernel
    (`kernels/decode_attention`, where this runs *inside* the Pallas body)
    both derive from it, so they cannot drift apart. Scores of −inf (or
    any non-finite) are invalid and never selected. Per row:

    1. bucketize scores into ``n_buckets`` linear ranges,
    2. per-bucket ≥-counts + cut bin where the high-to-low cumulative
       count first reaches K,
    3. entries above the cut bin rank first in ascending index order, then
       the cut bin fills the remainder (the ASIC's k-wide priority
       encoders).

    ``rank < k`` ⇔ selected; everything else gets the sentinel M + k + 1.

    Every op keeps 2-D (sublane, lane) shape so Mosaic can tile it on
    real TPU: the histogram's high-to-low cumulative count is computed
    directly as ``cnt_ge[r, b] = #{m : bucket[r, m] ≥ b}`` — a static
    loop over the ``n_buckets`` lanes of [R, M] broadcast-compares
    (replacing the old rank-3 [R, M, n_buckets] one-hot + flat cumsum) —
    and the index-order prefix sums run as f32 MXU dots against a
    triangular ones matrix (:func:`_cumsum_lanes`). All counts are exact
    in f32 (≪ 2²⁴), so the ranks are bitwise the flat-op ranks.
    """
    m = s.shape[-1]
    finite = jnp.isfinite(s)
    smin = jnp.min(jnp.where(finite, s, jnp.inf), -1, keepdims=True)
    smax = jnp.max(jnp.where(finite, s, -jnp.inf), -1, keepdims=True)
    span = jnp.maximum(smax - smin, 1e-9)
    bucket = jnp.clip(((s - smin) / span * n_buckets).astype(jnp.int32),
                      0, n_buckets - 1)
    bucket = jnp.where(finite, bucket, -1)          # invalid → below range

    # high-to-low cumulative count per bucket, 2-D throughout: one
    # [R, 1] lane-reduction per (static) bucket id
    cnt_ge = jnp.concatenate(
        [jnp.sum((bucket >= b).astype(jnp.int32), -1, keepdims=True)
         for b in range(n_buckets)], axis=-1)        # [R, n_buckets]
    reach = cnt_ge >= k
    bin_ids = jax.lax.broadcasted_iota(jnp.int32, reach.shape, 1)
    cut = jnp.where(jnp.any(reach, -1, keepdims=True),
                    jnp.max(jnp.where(reach, bin_ids, -1), -1, keepdims=True),
                    0)                               # [R, 1]

    above = bucket > cut
    at_cut = bucket == cut
    n_above = jnp.sum(above.astype(jnp.int32), -1, keepdims=True)
    rank_above = _cumsum_lanes(above.astype(jnp.float32)) - 1
    rank_cut = n_above + _cumsum_lanes(at_cut.astype(jnp.float32)) - 1
    big = m + k + 1
    rank = jnp.where(above, rank_above,
                     jnp.where(at_cut, rank_cut, big))
    return jnp.where(rank < k, rank, big).astype(jnp.int32)


def comparison_free_topk(scores: jax.Array, k: int,
                         n_buckets: int = DEFAULT_N_BUCKETS,
                         valid: jax.Array | None = None):
    """Select the top-k indices of ``scores`` [M] without pairwise compares.

    Emission order comes from :func:`comparison_free_rank`; this wrapper
    scatters the ranked indices into a dense [k] list. Returns
    (indices [k] int32, gate [k] bool).  With ``valid`` given, invalid
    positions never get selected.
    """
    m = scores.shape[-1]
    s = scores.astype(jnp.float32)
    if valid is not None:
        s = jnp.where(valid, s, -jnp.inf)
    rank = comparison_free_rank(s[None, :], k, n_buckets)[0]
    sel = rank < k
    out = jnp.zeros((k,), jnp.int32).at[jnp.where(sel, rank, k)].set(
        jnp.arange(m, dtype=jnp.int32), mode="drop")
    gate = jnp.arange(k) < jnp.minimum(jnp.sum(sel.astype(jnp.int32)), k)
    return out, gate


def block_reduce_scores(scores: jax.Array, block: int,
                        mode: str = "max") -> jax.Array:
    """Token scores [..., M] → block scores [..., M//block] (paper fetches
    candidate *blocks*, keeping KV reads contiguous)."""
    *lead, m = scores.shape
    assert m % block == 0, f"M={m} not a multiple of block={block}"
    s = scores.reshape(*lead, m // block, block)
    return jnp.max(s, axis=-1) if mode == "max" else jnp.sum(s, axis=-1)


def exact_topk(scores: jax.Array, k: int):
    """Comparator-based reference selector (oracle for recall tests)."""
    _, idx = jax.lax.top_k(scores, k)
    return idx


def kv_traffic_bytes(m: int, d: int, k: int, *, packed_features: bool = True,
                     with_lop: bool = True) -> int:
    """KV bytes fetched per (head, query) — the Fig. 8 traffic model.

    Without LOP: read all M keys + M values (int8).  With LOP: read the
    feature cache (4-bit packed → d/2 bytes/key) + K exact keys + K values.
    """
    if not with_lop:
        return 2 * m * d
    feat = m * (d // 2 if packed_features else d)
    return feat + 2 * k * d
