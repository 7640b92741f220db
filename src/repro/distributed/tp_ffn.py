"""Tensor parallelism for the fused serving FFN: shard the f axis.

The fused whole-FFN kernel (DESIGN.md §TINT-projection-fusion) computes
act(x·Wg)·(x·Wu) → in-VMEM absmax re-barrier → ·Wd in ONE launch, which
removed the legacy TP resharding point between the up and down
projections — every device computed the full hidden block. This module
restores tensor parallelism for the serving FFN (the ROADMAP item): a
``shard_map`` wrapper splits the hidden **f axis** across the model
axis, so each rank runs the SAME fused kernel over its own contiguous
f-shard — its slice of the gate‖up columns and the matching rows of the
down stream — and the partial down outputs ``psum`` back together
(dequantization is linear in the integer accumulator, so the sum of
per-shard dequantized partials is the full projection).

Layout: ``gu_packed [..., d//4, 2f]`` concatenates gate columns ‖ up
columns, so a naive split of the last axis would hand the first ranks
only gate columns. The wrapper views it as ``[..., d//4, segs, f]``
(segs = 2 gated, 1 ungated) and shards the trailing f axis — each rank
gets the SAME contiguous feature block of *both* streams, matching its
``down_packed`` row shard (packed rows r cover hidden features
4r..4r+3, so row-sharding by equal contiguous blocks lines up exactly).

Numerics caveat (recorded in DESIGN.md §Serving-API): the kernel's
hidden re-barrier runs per rank, so the absmax is over the rank's f/n
features instead of all f — a *finer* quantization grouping, not the
single-device grouping. Output therefore matches the unsharded kernel
bitwise only at model-axis size 1; at n > 1 it agrees to int8
quantization noise (the subprocess check bounds the relative error).
The SP decode path has no such caveat — attention scales are per token,
not sharded.

Opt-in is explicit (mirroring ``sp_axes`` for decode attention): wrap
the serving call in :func:`use_ffn_tp` under an active mesh; without
the context (or without a mesh, or when f does not divide) every
consumer falls back to the single-launch path unchanged — dry-runs and
single-device tests are untouched.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.partitioning import current_mesh
from repro.kernels import ops

_state = threading.local()


@contextmanager
def use_ffn_tp(axis: str = "model"):
    """Enable f-axis FFN sharding over mesh axis ``axis`` for the scope.
    Consumers (``core/qlinear.ffn_node_apply``) pick it up when a mesh
    is active and the shapes divide; otherwise they fall back."""
    prev = getattr(_state, "axis", None)
    _state.axis = axis
    try:
        yield
    finally:
        _state.axis = prev


def ffn_tp_axis() -> str | None:
    return getattr(_state, "axis", None)


def ffn_fused_tp(x, gu_packed, gu_scale, down_packed, down_scale, *,
                 gated: bool, act: str, mesh=None, axis: str = "model"):
    """The whole-FFN fused dispatch, f-sharded over ``mesh[axis]``.

    Same operands and result as :func:`repro.kernels.ops.ffn_fused`
    (leading expert dims ride along untouched); each rank launches the
    fused kernel on its f-shard and the down partials ``psum``.
    """
    mesh = mesh or current_mesh()
    assert mesh is not None and axis in mesh.axis_names, (mesh, axis)
    segs = 2 if gated else 1
    f = down_packed.shape[-2] * 4
    assert gu_packed.shape[-1] == segs * f, (gu_packed.shape, f, gated)

    # view gate‖up as [..., d//4, segs, f] so sharding the trailing axis
    # gives every rank a matching contiguous feature block of BOTH streams
    gu4 = gu_packed.reshape(*gu_packed.shape[:-1], segs, f)
    gs4 = jnp.broadcast_to(
        gu_scale.astype(jnp.float32),
        (*gu_scale.shape[:-1], segs * f)).reshape(
        *gu_scale.shape[:-1], segs, f)

    def spec(ndim: int, shard_at: int) -> P:
        entries = [None] * ndim
        entries[shard_at] = axis
        return P(*entries)

    rep = P()

    def body(x_, gu4_, gs4_, dn_, ds_):
        f_l = gu4_.shape[-1]
        gu_l = gu4_.reshape(*gu4_.shape[:-2], segs * f_l)
        gs_l = gs4_.reshape(*gs4_.shape[:-2], segs * f_l)
        part = ops.ffn_fused(x_, gu_l, gs_l, dn_, ds_, gated=gated,
                             act=act)
        return jax.lax.psum(part, axis)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(rep, spec(gu4.ndim, gu4.ndim - 1),
                  spec(gs4.ndim, gs4.ndim - 1),
                  spec(down_packed.ndim, down_packed.ndim - 2), rep),
        out_specs=rep, check_vma=False)
    return fn(x, gu4, gs4, down_packed, down_scale)


def maybe_shard_f(node, x, *, gated: bool, act: str):
    """Route a fused-FFN node through the f-sharded path when the
    :func:`use_ffn_tp` opt-in is active, a mesh with the axis exists and
    the down-stream rows divide; else return None (caller falls back)."""
    axis = ffn_tp_axis()
    if axis is None:
        return None
    mesh = current_mesh()
    if mesh is None or axis not in mesh.axis_names:
        return None
    n = int(mesh.shape[axis])
    if n <= 0 or node["down_packed"].shape[-2] % n:
        return None
    return ffn_fused_tp(x, node["gu_packed"], node["gu_scale"],
                        node["down_packed"], node["down_scale"],
                        gated=gated, act=act, mesh=mesh, axis=axis)
