"""What every architecture's random weights share: keys from the run's
seed, and the draws each layer's projections and norms make.

An architecture's module (``bench/models/<name>.py``) builds two forms
of its weights from these keys:

* a plain form, read by its plain reference: ternary values in
  {-1, 0, +1} as int8, one absmean-style scale γ per projection
  (:func:`gamma`), the norm parameters (:func:`norm_params`), and the
  embedding and LM head in float32;
* the serving format of ``repro.serving.quantize``, made on the device
  in one jitted call from the same plain values.

Both draw every value from the same keys: the embedding, head and final
norm from :func:`top_key`, layer ``l`` from ``layer_keys(seed_data(seed),
n_layers)[l]``. A projection of reduction width k gets γ = 0.8 / sqrt(k)
times a factor in [0.9, 1.1] drawn from the seed (the absmean of a
unit-variance init of the same width), so activations keep their scale
through the stack and every projection's γ differs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

FFN_ALIGN = 128          # the serving format's FFN hidden-width multiple
EMBED_STD = 0.02


def root_key(seed: int):
    """A threefry key from all 64 bits of ``seed`` (PRNGKey keeps 32)."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    data = np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(data), impl="threefry2x32")


def seed_data(seed: int):
    """The raw uint32 pair of :func:`root_key` (a jit argument)."""
    return jax.random.key_data(root_key(seed))


def _split(data):
    """→ (embed/head/final-norm key, key of the layer keys)."""
    top, layers = jax.random.split(
        jax.random.wrap_key_data(data, impl="threefry2x32"))
    return top, layers


def top_key(data):
    """The key of the embedding, the LM head and the final norm."""
    return _split(data)[0]


def layer_keys(data, n_layers: int):
    return jax.random.split(_split(data)[1], n_layers)


def frozen(arch: dict) -> tuple:
    """A hashable form of the scalar entries of ``arch`` (a static jit
    argument); :func:`thaw` inverts it."""
    return tuple(sorted((k, v) for k, v in arch.items()
                        if not isinstance(v, (dict, list))))


def thaw(items: tuple) -> dict:
    return dict(items)


def gamma(key, k: int):
    """A projection's scale at reduction width ``k``."""
    return (0.8 / np.sqrt(k)) * jax.random.uniform(key, (), jnp.float32,
                                                   0.9, 1.1)


def norm_params(key, d: int, kind: str) -> dict:
    """A norm's gain (and, for ``layernorm``, its bias) of width ``d``."""
    kg, kb = jax.random.split(key)
    out = {"g": 1.0 + 0.05 * jax.random.normal(kg, (d,), jnp.float32)}
    if kind == "layernorm":
        out["b"] = 0.05 * jax.random.normal(kb, (d,), jnp.float32)
    return out


def ternary(key, shape):
    """Ternary values uniform over {-1, 0, +1}, int8."""
    return jax.random.randint(key, shape, -1, 2, jnp.int8)
