"""Fused TINT projection kernels: absmax barrier → ternary GEMM → epilogue.

The standalone pipeline (jnp absmax quantize → ``ternary_matmul``
pallas_call → jnp dequant + bias + activation) round-trips HBM three
times per projection. The paper's system integration (§III) hinges on
exactly this seam: the absmax barrier *is* the cross-core interface, so
the quantize belongs inside the same kernel that consumes the int8
vector, and the nonlinear epilogue overlaps with the linear tiles. These
kernels run the whole chain in one ``pallas_call``:

``fused_qlinear``
    grid (E, m, n): at the first n-step of every (expert, m-block) the
    f32 activation tile is absmax-quantized **in VMEM** (bitwise
    :func:`repro.core.quantization.quantize` — the same function runs
    inside the kernel body, so kernel and oracle cannot drift); every
    n-step then unpacks a 2-bit code tile, runs the int8 MXU dot, and
    applies the fused epilogue — dequant by (x-scale · per-column γ),
    bias, optional activation — before the tile ever leaves VMEM.

``fused_ffn``
    grid (E, m, n_f + n_d): the whole FFN as ONE kernel. Steps j < n_f
    stream gate/up column blocks (two code streams over the same
    activation tile), apply act(gate)·up into a [bm, f] VMEM scratch;
    step j == n_f re-runs the absmax barrier on that scratch (the
    hidden vector's cross-core interface); steps j ≥ n_f stream the
    down-projection code blocks against the re-quantized hidden tile.
    No intermediate touches HBM.

Both kernels take a leading **expert grid axis** (E = 1 for plain
linears): MoE expert stacks ride the same packed-code stream with the
expert id as a third grid coordinate, replacing the one-pallas_call-per-
expert ``vmap`` dispatch.

Tiling is decode-shaped: ``bm`` follows the true row count (multiples of
8, not 128), so a GEMV-shaped decode step (m = B ≤ 8) stops padding its
batch rows to an MXU tile — the k-reduction runs as one full-width VMEM
dot per (m, n) cell, which is what makes the in-kernel barrier exact
(the row absmax needs the whole vector before any column block starts).

Sweepable variants (DESIGN.md §Autotuning)
------------------------------------------
``bkq`` — the two-pass k-tiled barrier. With ``bkq > 0`` the f32
activation tile is never fully VMEM-resident: the grid grows a leading
2·(k//bkq) streaming prefix whose first pass folds per-k-tile absmaxes
into the row maximum (f32 max is exact, so the tiled max IS the global
max) and whose second pass quantizes k-tiles into the int8 scratch the
GEMM steps consume. Because the scale and every rounded element are
identical, the variant is **bitwise** the single-pass barrier — it
lifts the d_model-beyond-VMEM limit ROADMAP carried since PR 4.
``eg`` — expert-group blocking: the expert grid axis steps ``eg``
experts per launch step (block (eg, bm, ·)), trading grid length for
per-step VMEM. Per-expert math is untouched, so any ``eg`` dividing E
is bitwise ``eg = 1``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantization import EPS, INT8_MAX, quantize
from repro.kernels.ternary_matmul import _unpack_codes

DEFAULT_BN = 128


def apply_act(y: jax.Array, act: str | None) -> jax.Array:
    """Fused epilogue nonlinearity (shared by kernel and oracle)."""
    if act is None:
        return y
    if act == "silu":
        return jax.nn.silu(y)
    if act == "gelu":
        return jax.nn.gelu(y)
    if act == "squared_relu":
        r = jnp.maximum(y, 0.0)
        return r * r
    raise ValueError(f"unknown activation {act!r}")


def _barrier(x, xq_ref, xs_ref):
    """In-VMEM absmax barrier — THE quantize, running inside the kernel."""
    qt = quantize(x)
    xq_ref[...] = qt.values
    xs_ref[...] = qt.scale


def _tiled_barrier_phases(j, nk, bkq, x_tile, xq_ref, xs_ref, am_ref):
    """The two-pass k-tiled barrier, shared by both fused kernels.

    Steps j < nk fold per-tile absmaxes into the running row maximum
    (f32 max is exact, so the folded max IS ``absmax_scale``'s global
    reduction); step j == nk freezes the scale; steps nk ≤ j < 2·nk
    round each k-tile with that frozen scale — element for element the
    same divide/round/clip :func:`repro.core.quantization.quantize`
    runs, so the variant is bitwise the single-pass barrier.
    """
    @pl.when(j == 0)
    def _init_amax():
        am_ref[...] = jnp.zeros_like(am_ref)

    @pl.when(j < nk)
    def _fold_absmax():
        am_ref[...] = jnp.maximum(
            am_ref[...], jnp.max(jnp.abs(x_tile()), -1, keepdims=True))

    @pl.when(j == nk)
    def _freeze_scale():
        xs_ref[...] = (jnp.maximum(am_ref[...], EPS).astype(jnp.float32)
                       / INT8_MAX)

    @pl.when(jnp.logical_and(j >= nk, j < 2 * nk))
    def _quantize_tile():
        q = jnp.clip(jnp.round(x_tile().astype(jnp.float32) / xs_ref[...]),
                     -INT8_MAX, INT8_MAX)
        xq_ref[:, pl.ds((j - nk) * bkq, bkq)] = q.astype(jnp.int8)


# ---------------------------------------------------------------------------
# fused_qlinear: quantize → GEMM → dequant(+bias)(+act), one pallas_call
# ---------------------------------------------------------------------------

def _qlinear_kernel(x_ref, wp_ref, sc_ref, *rest, k, bkq, nk, eg, act,
                    has_bias):
    b_ref = rest[0] if has_bias else None
    if bkq:
        o_ref, xq_ref, xs_ref, am_ref = rest[-4:]
    else:
        o_ref, xq_ref, xs_ref = rest[-3:]
    j = pl.program_id(2)
    nk2 = 2 * nk

    if bkq:
        for t in range(eg):
            _tiled_barrier_phases(j, nk, bkq, lambda t=t: x_ref[t],
                                  xq_ref.at[t], xs_ref.at[t], am_ref.at[t])
    else:
        @pl.when(j == 0)
        def _quantize_tile():
            for t in range(eg):
                _barrier(x_ref[t], xq_ref.at[t], xs_ref.at[t])

    @pl.when(j >= nk2)
    def _gemm():
        for t in range(eg):
            w = _unpack_codes(wp_ref[t], k)                # [k, bn] int8
            acc = jax.lax.dot(xq_ref[t], w,
                              preferred_element_type=jnp.int32)
            y = acc.astype(jnp.float32) * xs_ref[t] * sc_ref[t]
            if has_bias:
                y = y + b_ref[t]
            o_ref[t] = apply_act(y, act)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bkq", "eg", "act",
                                             "metadata", "interpret"))
def fused_qlinear(x: jax.Array, packed: jax.Array, scale: jax.Array,
                  bias: jax.Array | None = None, *, bm: int,
                  bn: int = DEFAULT_BN, bkq: int = 0, eg: int = 1,
                  act: str | None = None, metadata: tuple = (),
                  interpret: bool = False) -> jax.Array:
    """f32 x [E, m, k] × packed ternary [E, k//4, n] → f32 [E, m, n].

    ``scale`` f32 [E, 1, n] is the per-column weight γ row (a plain node
    broadcasts its scalar γ; a fused-QKV node carries one γ per segment);
    ``bias`` f32 [E, 1, n] or None. E = 1 for non-expert projections —
    the expert axis is the leading grid coordinate of one launch, not a
    vmap of launches. m and n must be multiples of (bm, bn); ops.py pads
    m and picks bn to divide n.

    ``bkq`` > 0 (a divisor of k) streams the barrier as the two-pass
    k-tiled variant — the f32 activation enters VMEM in [bm, bkq] tiles
    only; ``eg`` (a divisor of E) groups that many experts per grid
    step. Both are pure tiling knobs (DESIGN.md §Autotuning): any legal
    setting is bitwise ``bkq=0, eg=1``. ``metadata``: the launch's trace
    marks (``repro.core.marks.launch_metadata``).
    """
    e, m, k = x.shape
    n = packed.shape[-1]
    assert packed.shape[-2] * 4 == k, (packed.shape, k)
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    assert e % eg == 0, (e, eg)
    assert bkq == 0 or k % bkq == 0, (k, bkq)
    nk = k // bkq if bkq else 0
    nk2 = 2 * nk
    nd = n // bn

    def _kidx(j):
        if not bkq:
            return 0
        return jnp.clip(jnp.where(j < nk, j, j - nk), 0, nk - 1)

    def _nidx(j):
        return jnp.clip(j - nk2, 0, nd - 1) if bkq else j

    has_bias = bias is not None
    in_specs = [
        pl.BlockSpec((eg, bm, bkq if bkq else k),
                     lambda e, i, j: (e, i, _kidx(j))),
        pl.BlockSpec((eg, k // 4, bn), lambda e, i, j: (e, 0, _nidx(j))),
        pl.BlockSpec((eg, 1, bn), lambda e, i, j: (e, 0, _nidx(j))),
    ]
    operands = [x, packed, scale]
    if has_bias:
        in_specs.append(pl.BlockSpec((eg, 1, bn),
                                     lambda e, i, j: (e, 0, _nidx(j))))
        operands.append(bias)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    scratch_shapes = [
        pltpu.VMEM((eg, bm, k), jnp.int8),       # barriered activation tile
        pltpu.VMEM((eg, bm, 1), jnp.float32),    # its absmax scales
    ]
    if bkq:
        scratch_shapes.append(pltpu.VMEM((eg, bm, 1), jnp.float32))

    return pl.pallas_call(
        functools.partial(_qlinear_kernel, k=k, bkq=bkq, nk=nk, eg=eg,
                          act=act, has_bias=has_bias),
        grid=(e // eg, m // bm, nk2 + nd),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((eg, bm, bn), lambda e, i, j: (e, i, _nidx(j))),
        out_shape=jax.ShapeDtypeStruct((e, m, n), jnp.float32),
        scratch_shapes=scratch_shapes,
        interpret=interpret,
        metadata=dict(metadata) or None,
        **kwargs,
    )(*operands)


# ---------------------------------------------------------------------------
# fused_ffn: act(x·Wg)·(x·Wu) → barrier → ·Wd, one pallas_call
# ---------------------------------------------------------------------------

def _ffn_kernel(x_ref, up_ref, usc_ref, *rest, k, f, bf, bkq, nk, nf, nd,
                act, gated):
    if gated:
        g_ref, gsc_ref = rest[0], rest[1]
        rest = rest[2:]
    d_ref, dsc_ref = rest[0], rest[1]
    if bkq:
        (o_ref, xq_ref, xs_ref, h_ref, hq_ref, hs_ref,
         am_ref) = rest[2:]
    else:
        o_ref, xq_ref, xs_ref, h_ref, hq_ref, hs_ref = rest[2:]
    j = pl.program_id(2)
    nk2 = 2 * nk

    if bkq:
        _tiled_barrier_phases(j, nk, bkq, lambda: x_ref[0],
                              xq_ref, xs_ref, am_ref)
    else:
        @pl.when(j == 0)
        def _quantize_x():
            _barrier(x_ref[0], xq_ref, xs_ref)

    # ---- gate/up phase: one hidden column block per step, into scratch ----
    @pl.when(jnp.logical_and(j >= nk2, j < nk2 + nf))
    def _gate_up():
        uw = _unpack_codes(up_ref[0], k)
        u = jax.lax.dot(xq_ref[...], uw, preferred_element_type=jnp.int32)
        u = u.astype(jnp.float32) * xs_ref[...] * usc_ref[0]
        if gated:
            gw = _unpack_codes(g_ref[0], k)
            g = jax.lax.dot(xq_ref[...], gw,
                            preferred_element_type=jnp.int32)
            g = g.astype(jnp.float32) * xs_ref[...] * gsc_ref[0]
            hblk = apply_act(g, act) * u
        else:
            hblk = apply_act(u, act)
        h_ref[:, pl.ds((j - nk2) * bf, bf)] = hblk

    # ---- the hidden vector's own absmax barrier, still in VMEM ----
    @pl.when(j == nk2 + nf)
    def _quantize_h():
        _barrier(h_ref[...], hq_ref, hs_ref)

    # ---- down phase: re-quantized hidden tile × down code stream ----
    @pl.when(j >= nk2 + nf)
    def _down():
        dw = _unpack_codes(d_ref[0], f)
        y = jax.lax.dot(hq_ref[...], dw, preferred_element_type=jnp.int32)
        o_ref[0] = y.astype(jnp.float32) * hs_ref[...] * dsc_ref[0]


@functools.partial(jax.jit, static_argnames=("bm", "bf", "bn", "bkq", "act",
                                             "gated", "metadata",
                                             "interpret"))
def fused_ffn(x: jax.Array, gu_packed: jax.Array, gu_scale: jax.Array,
              down_packed: jax.Array, down_scale: jax.Array, *, bm: int,
              bf: int, bn: int, bkq: int = 0, act: str, gated: bool,
              metadata: tuple = (),
              interpret: bool = False) -> jax.Array:
    """The whole FFN as one launch: x [E, m, k] → f32 [E, m, d_out].

    gu_packed   uint8 [E, k//4, 2f] (gate cols ‖ up cols; [E, k//4, f]
                when not gated) — passed twice with offset index maps so
                a step's gate and up blocks stream from one array
    gu_scale    f32   [E, 1, 2f]   per-column γ rows (per-stream scalars
                broadcast at quantize_params time)
    down_packed uint8 [E, f//4, d_out]; down_scale f32 [E, 1, d_out]

    Grid (E, m//bm, f//bf + d_out//bn), with a 2·(k//bkq)-step two-pass
    barrier prefix when ``bkq`` > 0 (bitwise ``bkq=0``; the *hidden*
    barrier stays single-pass — its [bm, f] scratch lives in VMEM
    either way). The hidden scratch never leaves VMEM; its absmax
    barrier runs at the first down step. ``metadata``: the launch's
    trace marks (``repro.core.marks.launch_metadata``).
    """
    e, m, k = x.shape
    f = down_packed.shape[-2] * 4
    d_out = down_packed.shape[-1]
    assert gu_packed.shape[-2] * 4 == k, (gu_packed.shape, k)
    assert gu_packed.shape[-1] == (2 * f if gated else f), \
        (gu_packed.shape, f, gated)
    assert m % bm == 0 and f % bf == 0 and d_out % bn == 0, \
        (m, f, d_out, bm, bf, bn)
    assert bkq == 0 or k % bkq == 0, (k, bkq)
    nf, nd = f // bf, d_out // bn
    nk = k // bkq if bkq else 0
    nk2 = 2 * nk

    def _x_idx(e, i, j):
        if not bkq:
            return (e, i, 0)
        return (e, i, jnp.clip(jnp.where(j < nk, j, j - nk), 0, nk - 1))

    def _up_idx(e, i, j):
        base = (f // bf) if gated else 0
        return (e, 0, base + jnp.clip(j - nk2, 0, nf - 1))

    def _down_idx(e, i, j):
        return (e, 0, jnp.clip(j - nk2 - nf, 0, nd - 1))

    def _out_idx(e, i, j):
        return (e, i, jnp.clip(j - nk2 - nf, 0, nd - 1))

    in_specs = [
        pl.BlockSpec((1, bm, bkq if bkq else k), _x_idx),
        pl.BlockSpec((1, k // 4, bf), _up_idx),
        pl.BlockSpec((1, 1, bf), _up_idx),
    ]
    operands = [x, gu_packed, gu_scale]
    if gated:
        gate_idx = lambda e, i, j: (e, 0, jnp.clip(j - nk2, 0, nf - 1))
        in_specs += [pl.BlockSpec((1, k // 4, bf), gate_idx),
                     pl.BlockSpec((1, 1, bf), gate_idx)]
        operands += [gu_packed, gu_scale]
    in_specs += [pl.BlockSpec((1, f // 4, bn), _down_idx),
                 pl.BlockSpec((1, 1, bn), _down_idx)]
    operands += [down_packed, down_scale]

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    scratch_shapes = [
        pltpu.VMEM((bm, k), jnp.int8),       # barriered activation
        pltpu.VMEM((bm, 1), jnp.float32),
        pltpu.VMEM((bm, f), jnp.float32),    # hidden act(g)·u scratch
        pltpu.VMEM((bm, f), jnp.int8),       # its barriered form
        pltpu.VMEM((bm, 1), jnp.float32),
    ]
    if bkq:
        scratch_shapes.append(pltpu.VMEM((bm, 1), jnp.float32))

    return pl.pallas_call(
        functools.partial(_ffn_kernel, k=k, f=f, bf=bf, bkq=bkq, nk=nk,
                          nf=nf, nd=nd, act=act, gated=gated),
        grid=(e, m // bm, nk2 + nf + nd),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn), _out_idx),
        out_shape=jax.ShapeDtypeStruct((e, m, d_out), jnp.float32),
        scratch_shapes=scratch_shapes,
        interpret=interpret,
        metadata=dict(metadata) or None,
        **kwargs,
    )(*operands)
