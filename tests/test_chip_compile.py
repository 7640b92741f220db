"""Compile the served Pallas kernels for a TPU v5e at bitnet-3b widths.

Interpret mode, the CPU test path, runs a kernel body in Python and
accepts what Mosaic, the TPU's Pallas compiler, refuses: int8 vector
shifts, lane slices off a 128 multiple, blocks off the (8, 128) tile.
These tests hand the kernels to Mosaic for a described, unattached v5e
chip — nothing runs, so they check compilation only — at the widths
``chip_smoke.py`` serves: d 3200, 32 heads of dim 100, FFN hidden 8640
(padded to 8704), 2176- and 4096-token caches in 128-token LOP blocks;
and the LOP decode at stablelm-1.6b's head dim 64.

The topology is described inside a module fixture, never at import:
every test worker imports this file, and loading the TPU library is
left to the one worker that runs these tests. Where the TPU library is
installed, a topology that cannot be described fails the tests; only
where it is missing do they skip.
"""

import importlib.util

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels import ops
from repro.serving.cache import round_up
from repro.serving.lop_select import k_keep_blocks
from repro.serving.quantize import FFN_HIDDEN_ALIGN

CFG = get_config("bitnet-3b")
D, H, DH = CFG.d_model, CFG.n_heads, CFG.hd
F = round_up(CFG.d_ff, FFN_HIDDEN_ALIGN)
M = round_up(2048 + 1, CFG.lop_block)          # capacity at max_len 2048
B = 4                                          # decode lanes (slots)


@pytest.fixture(scope="module")
def one_chip():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("the TPU library is not installed")
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for(one_chip, monkeypatch):
    """Lower ``fn`` for the described chip with Mosaic (not interpret
    mode) → the compiled HLO text."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()
    return run


@pytest.mark.parametrize("m", [B, 128])
def test_fused_qkv_qlinear_compiles(compile_for, m):
    n = CFG.q_dim + 2 * CFG.kv_dim
    hlo = compile_for(
        lambda x, p, s: ops.qlinear_fused(x, p, s, impl="pallas"),
        ((m, D), jnp.float32), ((D // 4, n), jnp.uint8),
        ((1, n), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("m", [B, 300])
def test_rmsnorm_compiles(compile_for, m):
    hlo = compile_for(lambda x, g: ops.rmsnorm(x, g, impl="pallas"),
                      ((m, D), jnp.float32), ((D,), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("m", [B, 128])
def test_fused_ffn_compiles(compile_for, m):
    hlo = compile_for(
        lambda x, gu, gs, dp, ds: ops.ffn_fused(
            x, gu, gs, dp, ds, gated=True, act="silu", impl="pallas"),
        ((m, D), jnp.float32), ((D // 4, 2 * F), jnp.uint8),
        ((1, 2 * F), jnp.float32), ((F // 4, D), jnp.uint8),
        ((1, 1), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_fused_qkv_off_lane_grid_compiles(compile_for):
    """A fused QKV width off the 128-lane grid runs as one whole-dim
    block (reduced granite-moe: 96 + 48 + 48 columns)."""
    k, n = 128, 192
    hlo = compile_for(
        lambda x, p, s: ops.qlinear_fused(x, p, s, impl="pallas"),
        ((B, k), jnp.float32), ((k // 4, n), jnp.uint8),
        ((1, n), jnp.float32))
    assert "tpu_custom_call" in hlo


# decode lanes × cache capacity: the smoke's pool, one lane, and the
# widest pool at the longest cache served
DECODE_SHAPES = [(B, M), (1, 4096), (8, 4096)]


@pytest.mark.parametrize("b,m", DECODE_SHAPES)
@pytest.mark.parametrize("use_lop", [True, False], ids=["lop", "dense"])
def test_decode_attention_compiles(compile_for, use_lop, b, m):
    hkv = CFG.n_kv_heads
    hlo = compile_for(
        lambda qi, qs, k, v, ks, vs, f, nl: ops.decode_attention(
            qi, qs, k, v, ks, vs, f, nl, block=CFG.lop_block,
            k_keep=k_keep_blocks(CFG, m), use_lop=use_lop, impl="pallas"),
        ((b, H, DH), jnp.int8), ((b, H, 1), jnp.float32),
        ((b, hkv, m, DH), jnp.int8), ((b, hkv, m, DH), jnp.int8),
        ((b, hkv, m), jnp.float32), ((b, hkv, m), jnp.float32),
        ((b, hkv, m, DH // 2), jnp.uint8), ((b,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_lop_decode_compiles_at_stablelm_width(compile_for):
    """The LOP decode at stablelm-1.6b's head dim 64 (32 kv heads: one
    slot is 32 lanes, 4096-token cache), beside bitnet-3b's 100 above:
    the screen's two nibble-plane dots compile at both served widths."""
    cfg = get_config("stablelm-1.6b")
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    b, m = 1, round_up(4096 + 1, cfg.lop_block)
    hlo = compile_for(
        lambda qi, qs, k, v, ks, vs, f, nl: ops.decode_attention(
            qi, qs, k, v, ks, vs, f, nl, block=cfg.lop_block,
            k_keep=k_keep_blocks(cfg, m), use_lop=True, impl="pallas"),
        ((b, h, dh), jnp.int8), ((b, h, 1), jnp.float32),
        ((b, hkv, m, dh), jnp.int8), ((b, hkv, m, dh), jnp.int8),
        ((b, hkv, m), jnp.float32), ((b, hkv, m), jnp.float32),
        ((b, hkv, m, dh // 2), jnp.uint8), ((b,), jnp.int32))
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2


def test_lop_launches_keep_their_instruction_names(compile_for):
    """Both LOP launches still compile to instructions named
    ``fused_decode_attention.N`` (what a profiler trace calls them), and
    the instruction text (what a TPU trace labels the op with) carries
    each launch's phase, ``lop_screen`` or ``lop_exact``."""
    import re
    b, m, hkv = B, M, CFG.n_kv_heads
    hlo = compile_for(
        lambda qi, qs, k, v, ks, vs, f, nl: ops.decode_attention(
            qi, qs, k, v, ks, vs, f, nl, block=CFG.lop_block,
            k_keep=k_keep_blocks(CFG, m), use_lop=True, impl="pallas"),
        ((b, H, DH), jnp.int8), ((b, H, 1), jnp.float32),
        ((b, hkv, m, DH), jnp.int8), ((b, hkv, m, DH), jnp.int8),
        ((b, hkv, m), jnp.float32), ((b, hkv, m), jnp.float32),
        ((b, hkv, m, DH // 2), jnp.uint8), ((b,), jnp.int32))
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    phases = re.findall(
        r'%fused_decode_attention(?:\.\d+)? = [^\n]*custom-call\([^\n]*'
        r'kernel_metadata=\{\s*"scope":"(lop_\w+)"', hlo)
    assert sorted(phases) == ["lop_exact", "lop_screen"]
    # the candidate selection's fusions carry the screen's mark too
    assert 'scope="lop_screen"' in hlo


def test_prefill_attention_compiles(compile_for):
    c, hkv = CFG.lop_block, CFG.n_kv_heads
    hlo = compile_for(
        lambda qi, qs, k, v, ks, vs, nl, qo: ops.prefill_attention(
            qi, qs, k, v, ks, vs, nl, q_offset=qo, impl="pallas"),
        ((1, H, c, DH), jnp.int8), ((1, H, c), jnp.float32),
        ((1, hkv, M, DH), jnp.int8), ((1, hkv, M, DH), jnp.int8),
        ((1, hkv, M), jnp.float32), ((1, hkv, M), jnp.float32),
        ((1,), jnp.int32), ((), jnp.int32))
    assert "tpu_custom_call" in hlo
