"""Operations and bytes the served kernels need, from shapes: the rules
and the shared kernels. Each architecture's module
(``bench/models/<name>.py``) counts its own layers with them.

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
# the ternary projection kernels, per call
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
