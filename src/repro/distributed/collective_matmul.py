"""Ring collective matmul (compute/communication overlap).

The TPU expression of the paper's head-level pipelining idea at pod scale
(DESIGN.md §2 C4): instead of a blocking all-reduce after a row-parallel
matmul, partial products circulate a ``ppermute`` ring in chunks — chunk
``c``'s hop overlaps with chunk ``c+1``'s matmul, hiding ICI latency behind
MXU work. XLA's latency-hiding scheduler interleaves the independent chunk
streams.

``ring_reduce_matmul(x, w)`` computes ``Y = Σᵢ Xᵢ @ Wᵢ`` (X, W sharded on
the contraction dim over ``axis_name``) and is numerically identical to
``psum(x_loc @ w_loc)`` — equality is tested on an 8-device host mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def ring_reduce_matmul(x_loc: jax.Array, w_loc: jax.Array, axis_name: str,
                       *, chunks: int = 4) -> jax.Array:
    """x_loc [B, k_loc] @ w_loc [k_loc, n] summed over the mesh axis.

    The local matmul is split into ``chunks`` column chunks of the output;
    each finished chunk starts circulating the ring while the next chunk is
    still on the MXU.
    """
    n_ranks = jax.lax.axis_size(axis_name)
    n = w_loc.shape[-1]
    chunks = min(chunks, n)
    assert n % chunks == 0
    cw = n // chunks
    perm = [(i, (i + 1) % n_ranks) for i in range(n_ranks)]

    outs = []
    for c in range(chunks):
        partial = x_loc @ w_loc[:, c * cw:(c + 1) * cw]   # local chunk
        acc = partial
        for _ in range(n_ranks - 1):
            # the hop of chunk c overlaps with chunk c+1's matmul above
            acc = jax.lax.ppermute(acc, axis_name, perm) + partial
        outs.append(acc)
    return jnp.concatenate(outs, axis=-1)


def allgather_matmul(x_loc: jax.Array, w_loc: jax.Array,
                     axis_name: str) -> jax.Array:
    """Y_loc = AllGather(X) @ W_loc without materializing the full gather.

    x_loc [b_loc, k] (sharded on batch), w_loc [k, n_loc] (sharded on
    columns): each rank streams the other ranks' activation blocks around
    the ring, multiplying as blocks arrive. → [b_loc · n_ranks? no —
    Y partial rows [b_loc*n_ranks, n_loc] assembled ring-rotated]:
    returns [B, n_loc] with B = b_loc × n_ranks in ring order.
    """
    n_ranks = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n_ranks) for i in range(n_ranks)]
    b_loc = x_loc.shape[0]

    out = jnp.zeros((b_loc * n_ranks, w_loc.shape[-1]), x_loc.dtype)
    cur = x_loc
    for t in range(n_ranks):
        y = cur @ w_loc                            # block from rank (me-t)%n
        row = ((me - t) % n_ranks) * b_loc
        out = jax.lax.dynamic_update_slice(out, y, (row, 0))
        if t < n_ranks - 1:
            cur = jax.lax.ppermute(cur, axis_name, perm)  # overlaps next @
    return out
