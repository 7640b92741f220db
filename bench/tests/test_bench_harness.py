"""Whole runs of the harness on the CPU at a reduced size.

A cell made of data files alone (a configuration, a traffic mix and the
BENCHMARK.json entries, in a scratch directory) runs through the harness
with the chip check skipped and comes out correct; with the timed path
broken underneath (a token altered where it is produced, a decode step
that hands back its state unchanged) the same run comes out not
correct. A cell of a new architecture, whose model module is one more
file in that directory, comes out correct, and not correct with the
module's reference broken; the readers and the trace's name table take
the module's counts and kernel names. The command itself refuses to run
without a TPU and the Pallas kernels, and in a directory that holds only
the benchmark's files.
"""

import itertools
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import harness, models, readers
from bench import trace as T
from bench.models import dense

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**33 + 5
TINYARCH = r'''"""The dense model renamed, with a kernel name of its own."""
from bench.models.dense import *  # noqa: F401,F403

KERNELS = {"kernels": {"tiny_attention": [r"^%?tiny_attention(\.\d+)? ="]}}
'''


@pytest.fixture(scope="module")
def cell_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("cell")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test", "file": "test",
                        "reduced": [], "why": "test"}]
    spec["workloads"] = [{"name": f"{c}.mixed", "config": c,
                          "traffic": "mixed", "chips": 1, "why": "test"}
                         for c in ("tiny", "tinyarch")]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m["workloads"] = ["tiny.mixed", "tinyarch.mixed"]
    (d / "BENCHMARK.json").write_text(json.dumps(spec))
    (d / "configs").mkdir()
    conf = json.loads((ROOT / "bench/configs/bitnet-3b.json").read_text())
    conf["name"] = "tiny"
    conf["arch"].update(n_layers=2, d_model=128, n_heads=4, n_kv_heads=4,
                        head_dim=32, d_ff=200, vocab=512, lop_block=32)
    conf["server"].update(slots=3, max_len=255)
    # at width 128 one int8 rounding flip moves a logit by up to ~0.2
    # (the program and the reference round a few values apart); the
    # fp8 control reads CONTROL_MIN and more here (test below)
    conf["limits"] = {"max_logit_gap": 1.0}
    (d / "configs" / "tiny.json").write_text(json.dumps(conf))
    conf.update(name="tinyarch", model="tinyarch")
    (d / "configs" / "tinyarch.json").write_text(json.dumps(conf))
    (d / "models").mkdir()
    (d / "models" / "tinyarch.py").write_text(TINYARCH)
    (d / "traffic").mkdir()
    (d / "traffic" / "mixed.json").write_text(json.dumps({
        "loop": "closed", "clients": 3, "grid": 4,
        "prompt_len": {"dist": "uniform", "lo": 60, "hi": 180},
        "max_tokens": {"dist": "uniform", "lo": 20, "hi": 60}}))
    return d


def run(cell_dir, seconds=3.0, config="tiny", models_dir=None):
    return harness.run_cell(f"{config}.mixed", SEED, seconds, False,
                            t_process=time.monotonic(), root=cell_dir,
                            check_chip=False,
                            configs_dir=cell_dir / "configs",
                            traffic_dir=cell_dir / "traffic",
                            models_dir=models_dir)


def tinyarch(cell_dir):
    conf = harness.load_config("tinyarch", cell_dir / "configs")
    return conf, models.load(conf, cell_dir / "models")


def test_a_cell_from_data_files_alone(cell_dir, capsys):
    res = run(cell_dir)
    assert "inside the window: 0 programs compiled" in capsys.readouterr().err
    assert res["correct"] is True
    assert res["attempted"] >= 3 and res["failed"] == 0
    spec = json.loads((cell_dir / "BENCHMARK.json").read_text())
    assert set(res["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "compared"
    assert res["compared"]["max_logit_gap"]["value"] <= \
        res["compared"]["max_logit_gap"]["limit"]


def _altered_token(orig):
    def decode_step(self, pool, tokens, *a):
        toks, pool = orig(self, pool, tokens, *a)
        return (toks + 1) % self.cfg.vocab, pool
    return decode_step


def _state_unchanged(orig):
    def decode_step(self, pool, tokens, *a):
        kept = jax.tree.map(jnp.copy, pool)
        toks, _ = orig(self, pool, tokens, *a)
        return toks, kept
    return decode_step


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged],
                         ids=["token-altered", "state-unchanged"])
def test_a_broken_timed_path_is_not_correct(cell_dir, monkeypatch, fault):
    from repro.serving.api import PooledEngine
    monkeypatch.setattr(PooledEngine, "decode_step",
                        fault(PooledEngine.decode_step))
    res = run(cell_dir, seconds=2.0)
    assert res["correct"] is False
    assert res["compared"]["max_logit_gap"]["value"] > \
        res["compared"]["max_logit_gap"]["limit"]


def _calls(mod, name, monkeypatch):
    """Count the calls of ``mod.name`` from here on."""
    seen = []
    orig = getattr(mod, name)

    def counted(*a, **kw):
        seen.append(name)
        return orig(*a, **kw)
    monkeypatch.setattr(mod, name, counted)
    return seen


def test_a_new_architecture_from_new_files_alone(cell_dir, monkeypatch):
    conf, mod = tinyarch(cell_dir)
    assert Path(mod.__file__).parent == cell_dir / "models"
    built = _calls(mod, "served_params", monkeypatch)
    referenced = _calls(mod, "logits", monkeypatch)
    res = run(cell_dir, config="tinyarch", models_dir=cell_dir / "models")
    assert res["correct"] is True
    assert res["attempted"] >= 3 and res["failed"] == 0
    assert built == ["served_params"] and referenced


def _residual_dropped(layer, n_layers):
    """``layer`` with the residual of the last of ``n_layers`` layers
    left out of each forward pass."""
    calls = itertools.count()

    def broken(x, lw, n_prompt, **kw):
        out = layer(x, lw, n_prompt, **kw)
        return out - x if next(calls) % n_layers == n_layers - 1 else out
    return broken


def test_a_new_architecture_with_its_reference_broken(cell_dir,
                                                      monkeypatch):
    conf, _ = tinyarch(cell_dir)
    monkeypatch.setattr(dense, "_layer", _residual_dropped(
        dense._layer, conf["arch"]["n_layers"]))
    res = run(cell_dir, seconds=2.0, config="tinyarch",
              models_dir=cell_dir / "models")
    assert res["correct"] is False
    assert res["compared"]["max_logit_gap"]["value"] > \
        res["compared"]["max_logit_gap"]["limit"]


def test_readers_take_the_model_modules_counts_and_names(cell_dir,
                                                        monkeypatch):
    conf, mod = tinyarch(cell_dir)
    table = T.load_kernel_table(mod.KERNELS)
    assert table["kernels"]["tiny_attention"] == \
        mod.KERNELS["kernels"]["tiny_attention"]
    red = T.Reduction(
        window_s=1.0, busy_s=0.5,
        program_spans={"decode_step": [0.1] * 4}, whole_spans={},
        kernel_s={(k, "decode_step"): 0.01
                  for k in ("decode_attention", "qlinear", "ffn")},
        kernel_calls={("decode_attention", "decode_step"): 8,
                      ("qlinear", "decode_step"): 16,
                      ("ffn", "decode_step"): 8},
        top_ops=[], top_gaps=[])
    recs = [{"prompt_len": 100 + 10 * i, "stamps": [0.1, 0.2, 0.3]}
            for i in range(3)]
    ctx = harness.Context(conf=conf, run={"records": recs},
                          reduction=red, trace_span=(0.0, 1.0),
                          device_kind="TPU v5 lite", model=mod,
                          table=table)
    names = ("decode_attention_roofline", "decode_mfu",
             "projections_roofline")
    before = {n: harness.load_reader(n)(ctx) for n in names}
    assert all(v > 0 for v in before.values())
    kk, arch = readers.k_keep(ctx), conf["arch"]
    assert before["decode_mfu"] == pytest.approx(100 * sum(
        mod.decode_token_ops(arch, live, kk, True)
        for live in readers.decoded_live_lengths(ctx)) / 393e12)
    # the readers follow the module: twice the counts, twice the share
    def doubled(shapes):
        return [(2 * s[0], 2 * s[1]) + tuple(s[2:]) for s in shapes]
    att, tok, proj = (mod.decode_attention_calls, mod.decode_token_ops,
                      mod.decode_projection_calls)
    monkeypatch.setattr(mod, "decode_attention_calls",
                        lambda *a: doubled(att(*a)))
    monkeypatch.setattr(mod, "decode_token_ops", lambda *a: 2 * tok(*a))
    monkeypatch.setattr(mod, "decode_projection_calls", lambda *a: {
        k: doubled(v) for k, v in proj(*a).items()})
    for n in names:
        assert harness.load_reader(n)(ctx) == pytest.approx(2 * before[n])


def test_a_configuration_without_a_model_is_dense():
    for name in ("bitnet-3b", "stablelm-1.6b"):
        conf = harness.load_config(name)
        assert "model" not in conf and models.load(conf) is dense
    with pytest.raises(ValueError):
        models.load({"name": "x", "model": "../dense"})


def _command(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "bitnet-3b.chat-short", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    assert not any(line.strip().startswith("{")
                   for line in proc.stdout.splitlines())


@pytest.mark.parametrize("env", [{}, {"REPRO_KERNEL_IMPL": "ref"}],
                         ids=["cpu", "ref-kernels"])
def test_the_command_refuses_without_a_tpu(env):
    proc = _command(ROOT, env)
    _no_result(proc)
    assert "no result" in proc.stderr


def test_the_command_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_command(tmp_path))
