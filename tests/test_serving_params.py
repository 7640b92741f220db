"""The per-layer deployment builder (``serving.quantize.build_serving_params``).

A full-width server builds its ternary weights one layer at a time, so
the f32 master tree never exists at once. The result must be bitwise the
tree the two-step path gives: ``quantize_params(init_params(...))``.
Bitwise is defined under jit, as for every fused projection
(tests/test_qlinear_fused.py).
"""

import jax
import numpy as np
import pytest

from repro.launch.train import resolve_config
from repro.models.transformer import init_params
from repro.serving.quantize import build_serving_params, quantize_params


@pytest.mark.parametrize("arch", ["bitnet-3b", "granite-moe-1b-a400m"])
def test_build_serving_params_bitwise(arch):
    cfg = resolve_config(arch, True)
    key = jax.random.PRNGKey(3)
    want = jax.jit(lambda k: quantize_params(cfg, init_params(cfg, k)[0]))(key)
    got = build_serving_params(cfg, key)
    want_leaves, want_def = jax.tree_util.tree_flatten_with_path(want)
    got_leaves, got_def = jax.tree_util.tree_flatten(got)
    assert got_def == want_def
    for (path, w), g in zip(want_leaves, got_leaves):
        assert w.dtype == g.dtype and w.shape == g.shape, path
        assert np.array_equal(np.asarray(w), np.asarray(g)), \
            jax.tree_util.keystr(path)

