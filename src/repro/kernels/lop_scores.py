"""LOP surrogate-score Pallas kernel (paper §III-A, Fig. 4).

The ASIC's ExpAdd array accumulates ŝ(q,k) = Σ sgn·sgn·2^(LO+LO) with
barrel-shifted 1s. TPU adaptation: ŝ is exactly ``dot(pot(q), pot(k))``
(power-of-two rounding), so the screen is an int8 MXU matmul whose *key side
streams from the packed 4-bit feature cache* — (sgn‖LO) nibbles, two per
byte — halving screen-side HBM traffic vs int8 keys and ×4 vs bf16.

HW-codesign notes:
  * The feature tile enters VMEM packed (uint8, d/2 bytes per key) and is
    expanded nibble→pot-int8 *inside* VMEM; the MXU then performs the dot.
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

DEFAULT_BQ, DEFAULT_BM = 128, 512

LO_ZERO = 7


def _nibbles_to_pot(feat_packed: jax.Array, d: int) -> jax.Array:
    """uint8 [bm, d//2] packed (sgn‖LO) nibbles → int8 pot values [bm, d].

    Everything up to the final cast runs in int32: Mosaic cannot shift or
    lane-interleave int8 vectors.
    """
    fp = feat_packed.astype(jnp.int32)
    lo_nib = fp & 0xF
    hi_nib = (fp >> 4) & 0xF
    nib = jnp.stack([lo_nib, hi_nib], axis=-1).reshape(feat_packed.shape[0], d)
    lo = nib & 0x7
    sgn = (nib >> 3) & 0x1
    mag = jnp.where(lo == LO_ZERO, 0, jnp.left_shift(1, jnp.minimum(lo, 6)))
    return ((1 - 2 * sgn) * mag).astype(jnp.int8)


def _lop_scores_kernel(qp_ref, feat_ref, o_ref):
    """Grid (q-tile i, key-tile j): one int8 MXU dot per (i, j)."""
    qp = qp_ref[...]                                     # [bq, d] int8 (pot)
    kp = _nibbles_to_pot(feat_ref[...], qp.shape[-1])    # [bm, d] int8 (pot)
    o_ref[...] = jax.lax.dot_general(
        qp, kp, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)


@functools.partial(jax.jit, static_argnames=("bq", "bm", "interpret"))
def lop_scores_kernel(q_pot: jax.Array, feat_packed: jax.Array, *,
                      bq: int = DEFAULT_BQ, bm: int = DEFAULT_BM,
                      interpret: bool = False) -> jax.Array:
    """pot(q) int8 [g, d] × packed features uint8 [m, d//2] → int32 [g, m].

    ``g`` and ``m`` must be multiples of the block sizes (ops.py pads).
    """
    g, d = q_pot.shape
    m = feat_packed.shape[0]
    assert feat_packed.shape[1] * 2 == d
    assert g % bq == 0 and m % bm == 0, (g, m, bq, bm)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))

    return pl.pallas_call(
        _lop_scores_kernel,
        grid=(g // bq, m // bm),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, d // 2), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((g, m), jnp.int32),
        interpret=interpret,
        **kwargs,
    )(q_pot, feat_packed)
