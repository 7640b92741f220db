"""Operation and byte counts against hand counts at the published
shapes, and a roofline share that reads 100% when a kernel moves just
what the count says at the peak rate."""

import json
from pathlib import Path

import pytest

from bench import cost
from bench.models import dense

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
PK = {"int8_ops_per_s": 393e12, "bf16_flops_per_s": 197e12,
      "hbm_bytes_per_s": 819e9}


def arch(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["arch"]


def test_peaks_table_has_the_v5e_and_refuses_others():
    pk = cost.peaks("TPU v5 lite")
    assert pk["int8_ops_per_s"] == 393e12
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cost.peaks("cpu")


def test_projection_counts_bitnet():
    per = dense.projections_per_layer(arch("bitnet-3b"), rows=8)
    ops, nbytes = per["qkv"]
    assert ops == 2 * 8 * 3200 * 9600
    assert nbytes == 3200 * 9600 / 4 + 9600 * 4 + 8 * (3200 + 9600) * 4
    ops, nbytes = per["ffn"]
    assert ops == 2 * 8 * 3200 * 2 * 8640 + 2 * 8 * 8640 * 3200
    assert nbytes == 3 * 3200 * 8640 / 4 + 2 * 8640 * 4 + 8 * 2 * 3200 * 4
    # 26 layers of codes: 2.79e9 ternary weights, 0.70 GB at 2 bits
    assert dense.ternary_params(arch("bitnet-3b")) == 26 * (
        3200 * 9600 + 3200 * 3200 + 3 * 3200 * 8640)


def test_decode_attention_counts_stablelm():
    a = arch("stablelm-1.6b")                  # 32 heads of 64, block 128
    # 3000 live keys, k_keep 4: screen every key, exact 3 whole blocks
    # plus the partial last block (3000 - 23·128 = 56 keys)
    ops, nbytes = dense.decode_attention(a, 3000, 4)
    exact = 3 * 128 + 56
    assert ops == 4 * 32 * exact * 64 + 2 * 32 * 3000 * 64
    assert nbytes == (32 * exact * (2 * 64 + 8) + 32 * 64 * 5
                      + 32 * 3000 * 64 / 2)
    # within the kept budget the screen prunes nothing and is not counted
    ops, nbytes = dense.decode_attention(a, 300, 4)
    assert ops == 4 * 32 * 300 * 64
    assert nbytes == 32 * 300 * (2 * 64 + 8) + 32 * 64 * 5


def test_least_time_is_the_larger_bound():
    assert cost.least_s(393e12, 1.0, PK) == pytest.approx(1.0)
    assert cost.least_s(1.0, 819e9, PK) == pytest.approx(1.0)


def test_roofline_cannot_pass_100_when_the_count_is_right():
    a = arch("bitnet-3b")
    ops, nbytes = dense.projections_per_layer(a, 8)["ffn"]
    at_peak = nbytes / PK["hbm_bytes_per_s"]   # moved at full bandwidth
    assert 100 * cost.least_s(ops, nbytes, PK) / at_peak == \
        pytest.approx(100.0)
    for live in (100, 1536, 2047):
        ops, nbytes = dense.decode_attention(a, live, 2)
        t = max(ops / PK["int8_ops_per_s"], nbytes / PK["hbm_bytes_per_s"])
        assert cost.least_s(ops, nbytes, PK) <= t * (1 + 1e-12)


def test_decode_token_ops_bitnet():
    a = arch("bitnet-3b")
    att, _ = dense.decode_attention(a, 2000, 2)
    assert dense.decode_token_ops(a, 2000, 2) == (
        2 * dense.ternary_params(a) + 2 * 3200 * 32000 + 26 * att)
