"""Whole runs of the harness on the CPU at a reduced size.

A cell made of data files alone (a configuration, a traffic mix and the
BENCHMARK.json entries, in a scratch directory) runs through the harness
with the chip check skipped and comes out correct; with the timed path
broken underneath (a token altered where it is produced, a decode step
that hands back its state unchanged) the same run comes out not
correct. The command itself refuses to run without a TPU and the Pallas
kernels, and in a directory that holds only the benchmark's files.
"""

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

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**33 + 5


@pytest.fixture(scope="module")
def cell_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("cell")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test", "file": "test",
                        "reduced": [], "why": "test"}]
    spec["workloads"] = [{"name": "tiny.mixed", "config": "tiny",
                          "traffic": "mixed", "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m["workloads"] = ["tiny.mixed"]
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
    (d / "traffic").mkdir()
    (d / "traffic" / "mixed.json").write_text(json.dumps({
        "loop": "closed", "clients": 3, "grid": 4,
        "prompt_len": {"dist": "uniform", "lo": 60, "hi": 180},
        "max_tokens": {"dist": "uniform", "lo": 20, "hi": 60}}))
    return d


def run(cell_dir, seconds=3.0):
    return harness.run_cell("tiny.mixed", SEED, seconds, False,
                            t_process=time.monotonic(), root=cell_dir,
                            check_chip=False,
                            configs_dir=cell_dir / "configs",
                            traffic_dir=cell_dir / "traffic")


def test_a_cell_from_data_files_alone(cell_dir):
    res = run(cell_dir)
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
