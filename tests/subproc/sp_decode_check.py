"""Subprocess: SP quota-sharded decode ≡ local decode (8 host devices).

Checks, on a (data=2, model=4) mesh:
  1. keep=1.0 → SP decode output == local dense decode (exactness),
  2. cache write lands on the owner shard only,
  3. quota selection (keep<1) recall vs global top-K selection ≥ 70%.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.bitnet_3b import REDUCED
from repro.core.lop import lop_features, pack_features
from repro.distributed.partitioning import use_mesh
from repro.launch.mesh import make_host_mesh
from repro.serving.engine import lop_decode_attention
from repro.distributed.sp_decode import sp_decode_attention

rng = np.random.default_rng(0)
cfg = REDUCED.replace(lop_keep=1.0, lop_block=32)
B, H, Hkv, dh = 4, cfg.n_heads, cfg.n_kv_heads, cfg.hd
M = 512   # 16 blocks; 4 blocks per model shard

qi = jnp.asarray(rng.integers(-60, 61, (B, H, dh)), jnp.int8)
qsc = jnp.asarray(rng.uniform(0.005, 0.02, (B, H, 1)), jnp.float32)
ki = jnp.asarray(rng.integers(-60, 61, (B, Hkv, dh)), jnp.int8)
vi = jnp.asarray(rng.integers(-60, 61, (B, Hkv, dh)), jnp.int8)
ksc = jnp.asarray(rng.uniform(0.005, 0.02, (B, Hkv, 1)), jnp.float32)
vsc = jnp.asarray(rng.uniform(0.005, 0.02, (B, Hkv, 1)), jnp.float32)
feat_new = pack_features(lop_features(ki))

cl = {
    "k": jnp.asarray(rng.integers(-60, 61, (B, Hkv, M, dh)), jnp.int8),
    "v": jnp.asarray(rng.integers(-60, 61, (B, Hkv, M, dh)), jnp.int8),
    "k_scale": jnp.asarray(rng.uniform(0.005, 0.02, (B, Hkv, M)),
                           jnp.float32),
    "v_scale": jnp.asarray(rng.uniform(0.005, 0.02, (B, Hkv, M)),
                           jnp.float32),
}
cl["feat"] = pack_features(lop_features(cl["k"]))
lengths = jnp.full((B,), M - 40, jnp.int32)

mesh = make_host_mesh((2, 4), ("data", "model"))
with use_mesh(mesh):
    out_sp, cl_sp = jax.jit(lambda q, qs, c, ln: sp_decode_attention(
        cfg, q, qs, ki, vi, ksc, vsc, feat_new, c, ln, window=0,
        use_lop=True, sp_axes=("model",)))(qi, qsc, cl, lengths)

# local reference: write + dense attention (keep=1 → LOP is exact)
from repro.serving.engine import _kv_rows, _write_token
cl_local = _write_token(dict(cl), _kv_rows(ki, vi, ksc[..., 0], vsc[..., 0],
                                           feat_new), lengths)
out_local = lop_decode_attention(cfg, qi, qsc, cl_local, lengths + 1,
                                 window=0, use_lop=False)

err = float(jnp.max(jnp.abs(out_sp - out_local)))
ref = float(jnp.max(jnp.abs(out_local))) + 1e-9
assert err / ref < 1e-3, (err, ref)
print("sp==local exactness ok", err / ref)

# the write landed identically
for key in ("k", "v", "k_scale", "v_scale", "feat"):
    assert (np.asarray(cl_sp[key]) == np.asarray(cl_local[key])).all(), key
print("sp cache write ok")

# quota-sharded recall vs global selection at keep=0.25
cfg2 = cfg.replace(lop_keep=0.25)
with use_mesh(mesh):
    out_q, _ = jax.jit(lambda q, qs, c, ln: sp_decode_attention(
        cfg2, q, qs, ki, vi, ksc, vsc, feat_new, c, ln, window=0,
        use_lop=True, sp_axes=("model",)))(qi, qsc, cl, lengths)
out_g = lop_decode_attention(cfg2, qi, qsc, cl_local, lengths + 1,
                             window=0, use_lop=True)
out_d = out_local
rel_q = float(jnp.linalg.norm(out_q - out_d) / jnp.linalg.norm(out_d))
rel_g = float(jnp.linalg.norm(out_g - out_d) / jnp.linalg.norm(out_d))
print(f"keep=0.25: quota-sharded rel err {rel_q:.3f}, global rel err "
      f"{rel_g:.3f}")
assert rel_q < max(2.5 * rel_g, 0.35), (rel_q, rel_g)
print("SP_DECODE_CHECK_OK")
