"""The DeepSeek-V2 model module (``bench/models/deepseek_v2.py``): its
interface, its counts at the published widths, its served weights
against its plain ones layer for layer, and a tiny cell of it through
the harness on the CPU, correct, with the fp8 control not."""

import json
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness, models, stats
from bench.models import deepseek_v2 as M
from bench.reference import check
from repro.core.ternary import unpack_ternary

ROOT = Path(__file__).resolve().parents[2]
CONF = json.loads((ROOT / "bench/configs/deepseek-v2-lite.json").read_text())
ARCH = CONF["arch"]
SEED = 2**33 + 5
TINY = dict(ARCH, n_layers=3, d_model=64, n_heads=4, head_dim=24, d_ff=100,
            vocab=256, n_experts=8, top_k=2, d_expert=32, n_shared_experts=1,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, rope_original_max_pos=64, lop_block=32,
            lop_keep=0.25)


def test_interface_and_program_config():
    mod = models.load(CONF)
    assert mod is M and all(hasattr(mod, a) for a in models.INTERFACE)
    cfg = M.model_config(CONF)
    assert cfg.mla and cfg.latent_dim == 576 and cfg.n_kv_heads == 1
    assert (cfg.n_experts, cfg.top_k, cfg.d_expert) == (64, 6, 1408)
    assert cfg.first_k_dense == 1 and not cfg.norm_topk_prob
    # the catalog's keys, as published, beside the served arch
    assert CONF["reduced"] == [] and CONF["num_hidden_layers"] == 27
    assert CONF["moe_intermediate_size"] == ARCH["d_expert"]


def test_counts_at_published_widths():
    n = M.dims(ARCH)
    att = n["d"] * (n["q"] + n["dl"]) + n["dc"] * n["kb"] + n["o"] * n["d"]
    moe = n["e"] * 3 * n["d"] * n["fe"] + 3 * n["d"] * n["fs"]
    ternary = 27 * att + 3 * n["d"] * n["f"] + 26 * moe
    assert ternary == pytest.approx(15.28e9, rel=2e-3)
    assert ternary + 2 * n["d"] * n["v"] == pytest.approx(15.7e9, rel=1e-2)
    # 48 routings of 8 rows touch ~35 of 64 experts (2.16 MB each)
    assert M.distinct_experts(ARCH, 8) == pytest.approx(34.9, abs=0.05)
    ops, nbytes = M.moe_layer(ARCH, 8)
    assert nbytes == pytest.approx(34.9 * 2.163e6 + 4.33e6, rel=5e-3)
    assert ops == pytest.approx(2 * 8 * (6 * 8.65e6 + 17.3e6), rel=5e-3)
    # contexts under k_keep·block = 256 keep every row: read once
    ops, nbytes = M.mla_lane(ARCH, 200, 2)
    assert nbytes == pytest.approx(200 * (576 + 4) + 200 * 288
                                   + 16 * (128 + 128) * 4)
    assert M.mla_step(ARCH) == (0.0, 2 * 16 * 128 * 512 / 4)
    per = M.decode_projection_calls(ARCH, 8)
    assert len(per["qlinear"]) == 4 and len(per["ffn"]) == 2
    assert M.decode_token_ops(ARCH, 200, 2) > 2 * (
        27 * att + 26 * 3 * n["d"] * (6 * n["fe"] + n["fs"]))


def _codes(node, k):
    return np.asarray(unpack_ternary(node, k))


def test_served_weights_equal_plain_layer_for_layer():
    served = M.served_params(TINY, SEED)
    n = M.dims(TINY)
    keys = M.W.layer_keys(M.W.seed_data(SEED), TINY["n_layers"])
    for i, key in enumerate(keys):
        moe = i >= TINY["first_k_dense"]
        stack = served["layers" if moe else "dense_layers"]
        s = jax.tree.map(lambda a: a[i - moe * TINY["first_k_dense"]],
                         stack)
        p = M.plain_layer_jit(M.W.frozen(TINY), key, moe)
        a = s["attn"]
        qkva = _codes(a["wq_kva"]["packed"], n["d"])
        w = np.concatenate([p["wq"], p["wkva"]], 1)
        np.testing.assert_array_equal(qkva[:, :w.shape[1]], w)
        assert not qkva[:, w.shape[1]:].any()
        np.testing.assert_array_equal(
            np.asarray(a["wq_kva"]["scale"])[0, :w.shape[1]],
            np.r_[np.full(n["q"], p["g_q"]), np.full(n["dl"], p["g_kva"])])
        kb = np.asarray(p["wkb"]).reshape(n["dc"], n["h"], -1)
        for h in range(n["h"]):
            np.testing.assert_array_equal(
                _codes(a["wuk"]["packed"][h], n["dn"]), kb[:, h, :n["dn"]].T)
            np.testing.assert_array_equal(
                _codes(a["wuv"]["packed"][h], n["dc"]), kb[:, h, n["dn"]:])
        assert (np.asarray(a["wuk"]["scale"]) == p["g_kb"]).all()
        np.testing.assert_array_equal(_codes(a["wo"]["packed"], n["o"]),
                                      p["wo"])
        if not moe:
            f, fp = n["f"], n["fp"]
            gu = _codes(s["ffn"]["gu_packed"], n["d"])
            np.testing.assert_array_equal(gu[:, :f], p["ffn"]["wgu"][:, :f])
            np.testing.assert_array_equal(gu[:, fp:fp + f],
                                          p["ffn"]["wgu"][:, f:])
            continue
        m, fe, fep = p["moe"], n["fe"], n["fep"]
        np.testing.assert_array_equal(s["moe"]["router"], m["router"])
        for e in range(n["e"]):
            gu = _codes(s["moe"]["gu_packed"][e], n["d"])
            np.testing.assert_array_equal(gu[:, :fe], m["wgu"][e][:, :fe])
            np.testing.assert_array_equal(gu[:, fep:fep + fe],
                                          m["wgu"][e][:, fe:])
            np.testing.assert_array_equal(
                _codes(s["moe"]["down_packed"][e], fep)[:fe], m["wd"][e])
        np.testing.assert_array_equal(
            np.asarray(s["moe"]["down_scale"])[:, 0, 0], m["g_d"])


@pytest.fixture(scope="module")
def cell_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("dscell")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"] = [{"name": "tinyds.chat", "config": "tinyds",
                          "traffic": "chat", "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m["workloads"] = ["tinyds.chat"]
    (d / "BENCHMARK.json").write_text(json.dumps(spec))
    (d / "configs").mkdir()
    conf = dict(CONF, name="tinyds", arch=TINY)
    conf["server"] = dict(CONF["server"], slots=3, max_len=255)
    (d / "configs" / "tinyds.json").write_text(json.dumps(conf))
    (d / "traffic").mkdir()
    # contexts <= 60 under k_keep·block = 2 · 32, as the chip cell's
    # contexts stay under 2 · 128: the screen keeps every block
    (d / "traffic" / "chat.json").write_text(json.dumps({
        "loop": "closed", "clients": 3, "grid": 4,
        "prompt_len": {"dist": "uniform", "lo": 20, "hi": 40},
        "max_tokens": {"dist": "uniform", "lo": 10, "hi": 20}}))
    return d


def test_tiny_cell_correct(cell_dir):
    """Served through the harness (``PooledEngine``/``Scheduler`` over
    HTTP), a tiny DeepSeek cell comes out correct under the file's limit
    (the program reads 0.08–0.18 here, by which requests the window
    finishes)."""
    res = harness.run_cell("tinyds.chat", SEED, 3.0, False,
                           t_process=time.monotonic(), root=cell_dir,
                           check_chip=False,
                           configs_dir=cell_dir / "configs",
                           traffic_dir=cell_dir / "traffic")
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"itl_p50_ms", "setup_s"}


def test_tiny_cell_control_fails(cell_dir):
    """The fp8 control, judged on the served requests of a window by the
    same comparison, does not come out correct."""
    conf = harness.load_config("tinyds", cell_dir / "configs")
    mix = json.loads((cell_dir / "traffic" / "chat.json").read_text())
    served = harness.Served(conf, mix, SEED, M)
    try:
        served.warm_up()
        run = served.window(2.0)
    finally:
        served.close()
    assert stats.due_in_window(run)
    v = check.check(conf, mix, run, SEED, model=M, sample_tokens=300,
                    control=True)
    assert not v["ok"], v["read"]


def _op(name, start, end, marks):
    """An op as a TPU trace labels it: a launch carries its marks in its
    kernel metadata, a fusion as frontend attributes of its own."""
    from bench import trace as T
    if name.startswith("fused_"):
        body = ",\n".join(f'"{k}":"{v}"' for k, v in marks.items())
        attrs = f"kernel_metadata={{\n{body}\n}}"
    else:
        attrs = ",".join(f'{k}="{v}"' for k, v in marks.items())
    label = f"%{name} = f32[8,128] custom-call(%p), frontend_attributes={{{attrs}}}"
    return T.Event(label, float(start), float(end - start), label)


def test_scope_reader_on_a_synthetic_trace():
    """``scopes.scope_ms`` sums each decode step's ops marked with a
    scope — a launch nested in a LOP phase counts under the outer scope
    through ``within`` — over the steps wholly in the window."""
    from bench import scopes
    from bench import trace as T
    ev = lambda n, a, b: T.Event(n, float(a), float(b - a), n)
    dev = T.Device("/device:TPU:0")
    dev.modules = [ev("jit_step_greedy(1)", 0, 300),
                   ev("jit_step_greedy(1)", 450, 750),
                   ev("jit_step_greedy(1)", 900, 1100)]       # cut
    mla = {"scope": "mla_attention"}
    screen = {"scope": "lop_screen", "within": "mla_attention"}
    moe = {"scope": "moe_experts"}
    dev.ops = [_op("fused_qlinear.1", 10, 30, mla),
               _op("fused_decode_attention.8", 30, 80, screen),
               _op("fusion.3", 80, 90, screen),
               _op("fusion.4", 85, 95, mla),                   # overlaps
               _op("fused_ffn.2", 100, 200, moe),
               _op("fusion.9", 200, 210, moe),
               _op("fused_ffn.2", 500, 600, moe),
               _op("fused_decode_attention.9", 600, 640, screen),
               _op("fused_ffn.2", 950, 1000, moe)]
    tr = T.Trace([dev], [], 1000.0)
    progs = T.load_kernel_table()["programs"]
    assert scopes.scope_ms(tr, progs, "moe_experts") == (210 / 2 / 1e6, 2)
    assert scopes.scope_ms(tr, progs, "mla_attention") == (125 / 2 / 1e6, 2)
    assert scopes.scope_ms(tr, progs, "moe_route") is None
