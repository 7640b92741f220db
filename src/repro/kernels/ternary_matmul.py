"""TINT-core Pallas kernel: packed-2-bit ternary × int8 GEMM (paper §II-A).

HW-codesign notes (16 nm ASIC → TPU v5e):
  * The ASIC streams packed 2-bit codes into a multiplier-free 8×8
    select-accumulate array. On TPU we keep the *packed code stream* — the
    weight tile enters VMEM as uint8 codes (4 weights/byte, 4× less HBM
    traffic than int8) — and unpack to int8 **inside VMEM** before feeding
    the MXU, which does int8×int8 natively (select-accumulate would waste
    the systolic array).
  * Output-stationary mapping: the int32 accumulator tile lives in VMEM
    scratch across the k-reduction grid axis, exactly the OS dataflow the
    paper uses to keep partial sums local.
  * Block shapes default to (128, 512, 128): MXU-aligned (multiples of 128)
    and sized so x-tile (64 KiB) + packed-w-tile (16 KiB) + acc (64 KiB)
    fit comfortably in VMEM (the paper's 120 KB SRAM budget maps to the
    per-buffer VMEM working set).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM, DEFAULT_BK, DEFAULT_BN = 128, 512, 128


def _unpack_codes(wp: jax.Array, bk: int) -> jax.Array:
    """uint8 codes [bk//4, bn] → int8 ternary [bk, bn] (in-VMEM unpack).

    Shifts, masks, the row interleave and the subtraction run in int32:
    Mosaic has no int8 vector shift or subtract. One cast to int8 right
    before the MXU dot.
    """
    w = wp.astype(jnp.int32)
    parts = [(w >> (2 * j)) & 0x3 for j in range(4)]           # each [bk//4, bn]
    codes = jnp.stack(parts, axis=1).reshape(bk, wp.shape[-1])
    pos = (codes == 1).astype(jnp.int32)
    neg = (codes == 2).astype(jnp.int32)
    return (pos - neg).astype(jnp.int8)


def _ternary_matmul_kernel(x_ref, wp_ref, o_ref, acc_ref, *, n_k: int):
    """Grid (m, n, k); k is the sequential reduction axis (OS dataflow)."""
    kstep = pl.program_id(2)

    @pl.when(kstep == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                   # [bm, bk] int8
    w = _unpack_codes(wp_ref[...], x.shape[-1])      # [bk, bn] int8
    acc_ref[...] += jax.lax.dot(x, w, preferred_element_type=jnp.int32)

    @pl.when(kstep == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("k", "bm", "bk", "bn", "interpret"))
def ternary_matmul(x: jax.Array, packed: jax.Array, k: int, *,
                   bm: int = DEFAULT_BM, bk: int = DEFAULT_BK,
                   bn: int = DEFAULT_BN, interpret: bool = False) -> jax.Array:
    """int8 x [m, k] @ packed ternary [k//4, n] → int32 [m, n].

    Shapes must be multiples of the block sizes (ops.py pads).
    """
    m = x.shape[0]
    n = packed.shape[1]
    assert x.shape[1] == k and packed.shape[0] * 4 == k
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, bm, bk, bn)
    n_k = k // bk

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    return pl.pallas_call(
        functools.partial(_ternary_matmul_kernel, n_k=n_k),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk // 4, bn), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        **kwargs,
    )(x, packed)
