"""RMSNorm on fixed row tiles — one ``rows``-row tile per grid step.

Every grid step runs the same [rows, d] program, whatever the number of
rows, so a row's result depends on that row alone
(:func:`repro.kernels.ops.rmsnorm` says why the serving path needs it).

Semantics are :func:`repro.core.quantization.rmsnorm` (the ``ref``
oracle): x · rsqrt(mean(x²) + eps) · γ in f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, g_ref, o_ref, *, eps):
    x = x_ref[...]
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = x * jax.lax.rsqrt(ms + eps) * g_ref[...]


@functools.partial(jax.jit, static_argnames=("eps", "rows", "interpret"))
def rmsnorm_rows(x: jax.Array, g: jax.Array, *, eps: float, rows: int,
                 interpret: bool = False) -> jax.Array:
    """x f32 [n, d] (n a multiple of rows), γ f32 [1, d] → f32 [n, d]."""
    n, d = x.shape
    assert n % rows == 0, (n, rows)
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(n // rows,),
        in_specs=[pl.BlockSpec((rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        interpret=interpret,
    )(x, g)
