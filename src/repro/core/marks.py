"""Names for the device work of one part of a step, in a profiler trace.

A TPU trace names each device op by its HLO instruction text only, so
a part of a step is found by a mark the program writes there: inside
``with scope("moe_experts"):`` every op gets the op-name prefix
(``jax.named_scope``) and the frontend attribute ``scope=moe_experts``,
which the instruction text carries. A scope opened inside another
keeps the outer name as ``within`` (``within=mla_attention`` on the ops
of a ``lop_screen`` phase nested in it), so a reader finds each op
under every scope around it.

Pallas replaces a kernel launch's frontend attributes with its own
kernel metadata, so a launch carries the marks only as its
``metadata=``: :func:`launch_metadata` gives the marks in force where
the launch is traced (a static argument of a jitted kernel entry, so
the marks key its compile cache).
"""

from __future__ import annotations

import contextlib
import threading

import jax
from jax.experimental.xla_metadata import set_xla_metadata

_open = threading.local()


def _names() -> list:
    if not hasattr(_open, "names"):
        _open.names = []
    return _open.names


def _marks(names) -> dict:
    if not names:
        return {}
    out = {"scope": names[-1]}
    if len(names) > 1:
        out["within"] = "/".join(names[:-1])
    return out


@contextlib.contextmanager
def scope(name: str):
    """Mark the ops traced inside with ``name``; yields the marks as a
    dict for a Pallas launch's ``metadata=``."""
    names = _names()
    names.append(name)
    try:
        marks = _marks(names)
        with jax.named_scope(name), set_xla_metadata(**marks):
            yield dict(marks)
    finally:
        names.pop()


def launch_metadata() -> tuple:
    """The marks in force, as sorted (key, value) pairs (hashable: a
    static jit argument); empty outside every scope."""
    return tuple(sorted(_marks(_names()).items()))
