"""LOP surrogate-score Pallas kernel (paper §III-A, Fig. 4).

The ASIC's ExpAdd array accumulates ŝ(q,k) = Σ sgn·sgn·2^(LO+LO) with
barrel-shifted 1s. TPU adaptation: ŝ is exactly ``dot(pot(q), pot(k))``
(power-of-two rounding), so the screen is an int8 MXU matmul whose *key side
streams from the packed 4-bit feature cache* — (sgn‖LO) nibbles, two per
byte — halving screen-side HBM traffic vs int8 keys and ×4 vs bf16.

HW-codesign notes:
  * The feature tile enters VMEM packed (uint8, d/2 bytes per key); its
    two nibble planes expand to pot-int8 *inside* VMEM separately, and two
    int8 MXU dots score them against q's even and odd columns, with no
    lane interleave (:func:`nibble_planes_to_pot` says why).
  * Grid is (q-tiles, m-tiles); the m axis is the streaming axis — each step
    scores one contiguous block of cached keys, matching the ASIC's
    streamed one-pass accumulation.
  * Default blocks (128 q × 512 keys) keep the working set ≈
    128·d + 512·d/2 + 128·512·4 bytes ≤ VMEM for d ≤ 256, MXU-aligned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lop import features_to_pot

DEFAULT_BQ, DEFAULT_BM = 128, 512


def nibble_planes_to_pot(
        feat_packed: jax.Array) -> tuple[jax.Array, jax.Array]:
    """uint8 [bm, d/2] packed (sgn‖LO) nibbles → pot-int8 of the low and
    the high nibble plane, [bm, d/2] each: features 0, 2, 4, … and 1, 3,
    5, … (``core.lop.pack_features``'s layout).

    The bytes widen to int32 before any shift: Mosaic cannot shift int8
    vectors. The planes stay apart: interleaving them back into [bm, d]
    lowers to thousands of relayout ops (sublane shuffles, gathers) a
    block in Mosaic, while each plane decodes element-wise.
    """
    fp = feat_packed.astype(jnp.int32)
    return features_to_pot(fp & 0xF), features_to_pot(fp >> 4)


def plane_scores(q_even: jax.Array, q_odd: jax.Array,
                 feat_packed: jax.Array) -> jax.Array:
    """pot(q) at its even and odd columns, int8 [g, d/2] each, × packed
    features uint8 [bm, d/2] → surrogate scores int32 [g, bm]: two int8
    MXU dots, one a nibble plane. The int32 sum holds the products of
    ``dot(pot(q), pot(k))ᵀ``, grouped by plane, so the scores are bitwise
    equal."""
    k_even, k_odd = nibble_planes_to_pot(feat_packed)
    dot = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)
    return dot(q_even, k_even) + dot(q_odd, k_odd)


def _lop_scores_kernel(qe_ref, qo_ref, feat_ref, o_ref):
    """Grid (q-tile i, key-tile j): two int8 MXU dots per (i, j)."""
    o_ref[...] = plane_scores(qe_ref[...], qo_ref[...], feat_ref[...])


@functools.partial(jax.jit, static_argnames=("bq", "bm", "interpret"))
def lop_scores_kernel(q_even: jax.Array, q_odd: jax.Array,
                      feat_packed: jax.Array, *, bq: int = DEFAULT_BQ,
                      bm: int = DEFAULT_BM,
                      interpret: bool = False) -> jax.Array:
    """pot(q) at its even and odd columns, int8 [g, d/2] each, × packed
    features uint8 [m, d/2] → int32 [g, m].

    ``g`` and ``m`` must be multiples of the block sizes (ops.py pads).
    """
    g, dh = q_even.shape
    m = feat_packed.shape[0]
    assert q_odd.shape == q_even.shape and feat_packed.shape[1] == dh
    assert g % bq == 0 and m % bm == 0, (g, m, bq, bm)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))

    q_spec = pl.BlockSpec((bq, dh), lambda i, j: (i, 0))
    return pl.pallas_call(
        _lop_scores_kernel,
        grid=(g // bq, m // bm),
        in_specs=[q_spec, q_spec,
                  pl.BlockSpec((bm, dh), lambda i, j: (j, 0))],
        out_specs=pl.BlockSpec((bq, bm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((g, m), jnp.int32),
        interpret=interpret,
        **kwargs,
    )(q_even, q_odd, feat_packed)
