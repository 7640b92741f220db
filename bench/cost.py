"""Operations and bytes the served kernels need, from shapes.

Counts are what the algorithm has to do and move, not what a kernel
happens to touch: the decode screen reads the 4-bit features of the
live keys only, the exact phase the K/V rows of the kept blocks that lie
below the live length, a projection its packed ternary codes (at the
published hidden width, not the padded one) and its activation rows. A
kernel that moves more than this (padding, dead blocks, a second pass)
reads below 100% of its roofline; one that reads above it would mean a
count here is too high.

Peaks come from ``bench/peaks.json``, keyed by the device kind that JAX
reports; an unknown device is an error.
"""

from __future__ import annotations

import json
from pathlib import Path

from bench import weights as W

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
F32 = 4


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def least_s(ops: float, nbytes: float, pk: dict) -> float:
    """The least time the chip could take: the larger of int8 operations
    over the int8 peak and bytes over HBM bandwidth."""
    return max(ops / pk["int8_ops_per_s"], nbytes / pk["hbm_bytes_per_s"])


# ---------------------------------------------------------------------------
# projections (fused QKV, O, fused FFN), per call
# ---------------------------------------------------------------------------

def qlinear(rows: int, k: int, n: int) -> tuple[float, float]:
    """(int8 ops, bytes) of one fused ternary projection: x f32 [rows, k]
    in, packed codes k/4 × n and a γ row, y f32 [rows, n] out."""
    ops = 2.0 * rows * k * n
    nbytes = k * n / 4 + n * F32 + rows * (k + n) * F32
    return ops, nbytes


def ffn(rows: int, d: int, f: int) -> tuple[float, float]:
    """(int8 ops, bytes) of the fused gated FFN at hidden width f."""
    ops = 2.0 * rows * d * 2 * f + 2.0 * rows * f * d
    nbytes = (d * 2 * f + f * d) / 4 + 2 * f * F32 + rows * 2 * d * F32
    return ops, nbytes


def projections_per_layer(arch: dict, rows: int) -> dict:
    n = W.dims(arch)
    return {"qkv": qlinear(rows, n["d"], n["qkv"]),
            "o": qlinear(rows, n["q"], n["d"]),
            "ffn": ffn(rows, n["d"], n["f"])}


# ---------------------------------------------------------------------------
# decode attention (LOP screen + exact phase), per lane and step
# ---------------------------------------------------------------------------

def decode_attention(arch: dict, live: int, k_keep: int,
                     use_lop: bool = True) -> tuple[float, float]:
    """(int8 ops, bytes) of one lane's decode attention at ``live`` valid
    cache positions: the screen over every live key's packed features,
    then exact attention over the live keys of the kept blocks."""
    n = W.dims(arch)
    h, hkv, dh = arch["n_heads"], arch["n_kv_heads"], n["hd"]
    block = arch["lop_block"]
    exact = live
    if use_lop and live > k_keep * block:
        # the fewest live keys k_keep blocks can hold: the partial last
        # block and k_keep - 1 whole ones
        exact = (k_keep - 1) * block + (live - (live - 1) // block * block)
    kv_row = 2 * dh + 2 * F32                      # int8 K, V + 2 scales
    ops = 4.0 * h * exact * dh
    nbytes = hkv * exact * kv_row + h * dh * (1 + F32)   # q int8, out f32
    if use_lop and live > k_keep * block:       # the screen can prune
        ops += 2.0 * h * live * dh
        nbytes += hkv * live * dh / 2
    return ops, nbytes


# ---------------------------------------------------------------------------
# a whole decode step: model operations per token
# ---------------------------------------------------------------------------

def ternary_params(arch: dict) -> int:
    n = W.dims(arch)
    per_layer = n["d"] * n["qkv"] + n["q"] * n["d"] + 3 * n["d"] * n["f"]
    return arch["n_layers"] * per_layer


def decode_token_ops(arch: dict, live: int, k_keep: int,
                     use_lop: bool = True) -> float:
    """Operations one decoded token needs: 2 per ternary weight, 2 per
    LM-head weight, and the attention of every layer at ``live``."""
    n = W.dims(arch)
    att, _ = decode_attention(arch, live, k_keep, use_lop)
    return (2.0 * ternary_params(arch) + 2.0 * n["d"] * n["v"]
            + arch["n_layers"] * att)
