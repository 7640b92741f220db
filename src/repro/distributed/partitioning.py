"""Logical-axis partitioning (DP / FSDP / TP / EP / SP) — DESIGN.md §5.

Model code annotates tensors with *logical* axes; this module resolves them
against the active mesh:

  ``dp``    data parallel — batch dim; ``("data",)`` single-pod,
            ``("pod", "data")`` multi-pod.
  ``fsdp``  ZeRO-3 parameter/optimizer sharding — same mesh axes as ``dp``
            (parameters are all-gathered per scan step by XLA).
  ``tp``    tensor parallel — ``("model",)``: attention heads, FFN hidden,
            vocab, expert-internal dims.
  ``ep``    expert parallel — ``("model",)`` when n_experts divides the axis.
  ``sp``    sequence parallel — ``("model",)``: KV-cache / sequence dim for
            decode and long-context attention.

When no mesh is active every annotation is a no-op, so the exact same model
code runs single-device tests and 512-chip dry-runs.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

_state = threading.local()


def current_mesh() -> Mesh | None:
    return getattr(_state, "mesh", None)


@contextmanager
def use_mesh(mesh: Mesh | None):
    """Activate a mesh for logical-axis resolution (and as jax's mesh ctx)."""
    prev = current_mesh()
    _state.mesh = mesh
    try:
        if mesh is not None:
            with mesh:
                yield mesh
        else:
            yield None
    finally:
        _state.mesh = prev


def dp_axes(mesh: Mesh | None = None) -> tuple:
    mesh = mesh or current_mesh()
    if mesh is not None and "pod" in mesh.axis_names:
        return ("pod", "data")
    return ("data",)


fsdp_axes = dp_axes


def _resolve_axis(logical, mesh: Mesh | None):
    """logical axis name (or None / tuple of mesh axes) → mesh axes entry."""
    if logical is None:
        return None
    if logical == "dp" or logical == "fsdp":
        return dp_axes(mesh)
    if logical in ("tp", "ep", "sp"):
        return "model"
    # raw mesh axis names pass through ("data", "model", "pod", tuples)
    return logical


def logical_to_pspec(axes: tuple, mesh: Mesh | None = None) -> P:
    """("fsdp", "tp") → PartitionSpec(("data",), "model") etc."""
    mesh = mesh or current_mesh()
    return P(*[_resolve_axis(a, mesh) for a in axes])


def shard(x: jax.Array, *axes) -> jax.Array:
    """Constrain ``x`` to the resolved logical spec (no-op without a mesh).

    An axis entry may be a logical name, a raw mesh axis, or None; trailing
    dims may be omitted (treated as None).
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = logical_to_pspec(tuple(axes) + (None,) * (x.ndim - len(axes)), mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def is_spec_leaf(t) -> bool:
    """Logical-axis tuples are leaves; NamedTuples (pytree nodes) are not."""
    return (isinstance(t, tuple) and not hasattr(t, "_fields")) or t is None


def tree_pspecs(spec_tree, mesh: Mesh | None = None):
    """Map a tree of logical-axis tuples → tree of PartitionSpecs."""
    mesh = mesh or current_mesh()
    return jax.tree.map(
        lambda axes: logical_to_pspec(axes, mesh),
        spec_tree, is_leaf=is_spec_leaf,
    )


def named_shardings(spec_tree, mesh: Mesh | None = None):
    """Tree of logical-axis tuples → tree of NamedShardings (for jit args)."""
    mesh = mesh or current_mesh()
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, logical_to_pspec(axes, mesh)),
        spec_tree, is_leaf=is_spec_leaf,
    )
