"""kernels/autotune.py: candidates, table I/O, and the dispatch lookup.

The autotuning contract (DESIGN.md §Autotuning) has three legs:

  1. ``candidates()`` only emits LEGAL tilings (the divisibility screen)
     whose one-grid-step footprint fits the roofline VMEM budget, with
     the hardcoded default always candidate 0 — a sweep can never
     regress dispatch below the status quo;
  2. ``lookup()`` precedence: override context → table entry
     (``REPRO_TUNE=0`` disables) → ``{}``; a stale/illegal table entry
     falls through to ``{}`` instead of crashing dispatch;
  3. ``validate_table()`` is the CI gate's static half: structural
     problems in a persisted ``TUNE_*.json`` surface as strings, and a
     missing table is fine (the fallback IS the contract).

Plus the wiring: ``ops`` consults ``lookup()`` at dispatch, so an
``override`` context changes a real dispatch's tiling without changing
its bits.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.roofline import vmem_budget
from repro.core.ternary import make_ternary_weight
from repro.kernels import autotune, ops

DIMS = {
    "ternary_matmul": {"m": 8, "k": 256, "n": 256},
    "qlinear": {"e": 2, "m": 8, "k": 256, "n": 256},
    "ffn": {"e": 1, "m": 8, "k": 256, "f": 512, "n": 256},
    "prefill": {"bhg": 2, "r": 64, "d": 64, "m": 256, "chunk": 32},
    "decode": {"bhg": 2, "g": 2, "d": 64, "m": 256, "block": 64,
               "k_keep": 2},
}


# ---------------------------------------------------------------------------
# Candidate generation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernel", autotune.KERNELS)
def test_candidates_legal_unique_within_budget(kernel):
    dims = DIMS[kernel]
    cands = autotune.candidates(kernel, dims, max_candidates=12)
    assert 1 <= len(cands) <= 12
    seen = []
    for p in cands:
        assert set(p) == set(autotune.KERNEL_PARAMS[kernel]), p
        assert autotune.valid_params(kernel, dims, p), p
        assert p not in seen, p
        seen.append(p)
    # every swept (non-default) candidate fits the roofline VMEM budget
    for p in cands[1:]:
        assert autotune._tile_footprint(kernel, dims, p) <= vmem_budget()


def test_candidate_zero_is_the_hardcoded_default():
    """The sweep always times the status-quo tiling first."""
    q = autotune.candidates("qlinear", DIMS["qlinear"])[0]
    assert q == {"bm": 8, "bn": 128, "bkq": 0, "eg": 1}
    d = autotune.candidates("decode", DIMS["decode"])[0]
    assert d == {"n_slots": 2}
    p = autotune.candidates("prefill", DIMS["prefill"])[0]
    assert p == {"block": 128, "bq": 0}


def test_max_candidates_caps_the_sweep():
    cands = autotune.candidates("ffn", DIMS["ffn"], max_candidates=3)
    assert len(cands) == 3


# ---------------------------------------------------------------------------
# Keys and the legality screen
# ---------------------------------------------------------------------------

def test_shape_key_uses_declared_dim_order():
    key = autotune.shape_key("qlinear", {"n": 4, "k": 3, "m": 2, "e": 1})
    assert key == "e=1,m=2,k=3,n=4"
    with pytest.raises(AssertionError):
        autotune.shape_key("qlinear", {"m": 2})


def test_config_key_tracks_backend():
    ck = autotune.config_key()
    if jax.default_backend() == "tpu":
        assert ck == "tpu"
    else:
        assert ck.endswith("-interpret")


@pytest.mark.parametrize("kernel,params,ok", [
    ("qlinear", {"bm": 8, "bn": 128, "bkq": 128, "eg": 2}, True),
    ("qlinear", {"bm": 12, "bn": 128}, False),      # bm not a multiple of 8
    ("qlinear", {"bn": 7}, False),                  # 7 does not divide n
    ("qlinear", {"bn": 32}, False),                 # not a 128-lane block
    ("qlinear", {"bkq": 24}, False),                # 24 does not divide k
    ("qlinear", {"eg": 3}, False),                  # 3 does not divide e
    ("qlinear", {"nope": 1}, False),                # unknown knob
    ("qlinear", "bm=8", False),                     # not a dict
    ("ffn", {"bm": 8, "bf": 128, "bn": 256, "bkq": 0}, True),
    ("ffn", {"bf": 7}, False),
    ("ffn", {"bf": 64}, False),                     # not a 128-lane block
    ("prefill", {"block": 32, "bq": 8}, True),
    ("prefill", {"block": 32, "bq": 7}, False),     # 7 does not divide r
    ("prefill", {"block": 0}, False),
    ("decode", {"n_slots": 1}, True),
    ("decode", {"n_slots": 4}, False),              # Mosaic buffers ≤ 2
    ("decode", {"n_slots": 0}, False),
    ("ternary_matmul", {"bm": 8, "bk": 64, "bn": 32}, True),
    ("ternary_matmul", {"bk": 7}, False),
])
def test_valid_params_screen(kernel, params, ok):
    dims = {k: v for k, v in DIMS[kernel].items()}
    dims.update({"e": 2} if kernel == "qlinear" else {})
    assert autotune.valid_params(kernel, dims, params) is ok


# ---------------------------------------------------------------------------
# Table I/O + lookup precedence
# ---------------------------------------------------------------------------

QDIMS = {"e": 1, "m": 8, "k": 256, "n": 256}


def _mk_table(tmp_path, monkeypatch, params, *, us=1.0):
    path = tmp_path / "TUNE_test.json"
    monkeypatch.setenv("REPRO_TUNE_TABLE", str(path))
    table = {"version": autotune.TABLE_VERSION,
             "configs": {autotune.config_key(): {"qlinear": {
                 autotune.shape_key("qlinear", QDIMS):
                     {"params": params, "us": us}}}}}
    autotune.save_table(table, path)
    return path


def test_lookup_hits_table_misses_other_shapes(tmp_path, monkeypatch):
    _mk_table(tmp_path, monkeypatch, {"bm": 8, "bn": 128, "bkq": 128,
                                      "eg": 1})
    assert autotune.lookup("qlinear", QDIMS) == \
        {"bm": 8, "bn": 128, "bkq": 128, "eg": 1}
    other = dict(QDIMS, m=16)
    assert autotune.lookup("qlinear", other) == {}
    assert autotune.lookup("ffn", DIMS["ffn"]) == {}


def test_repro_tune_0_disables_the_table(tmp_path, monkeypatch):
    _mk_table(tmp_path, monkeypatch, {"bn": 128})
    monkeypatch.setenv("REPRO_TUNE", "0")
    assert autotune.lookup("qlinear", QDIMS) == {}


def test_override_beats_table_and_restores(tmp_path, monkeypatch):
    _mk_table(tmp_path, monkeypatch, {"bn": 128})
    with autotune.override("qlinear", bm=8, bn=256):
        assert autotune.lookup("qlinear", QDIMS) == {"bm": 8, "bn": 256}
        with autotune.override("qlinear", bm=16):
            assert autotune.lookup("qlinear", QDIMS) == {"bm": 16}
        assert autotune.lookup("qlinear", QDIMS) == {"bm": 8, "bn": 256}
    assert autotune.lookup("qlinear", QDIMS) == {"bn": 128}


def test_illegal_override_falls_through_to_defaults(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_TABLE", str(tmp_path / "absent.json"))
    with autotune.override("qlinear", bn=7):     # 7 does not divide n
        assert autotune.lookup("qlinear", QDIMS) == {}


def test_stale_table_entry_falls_through_and_is_flagged(tmp_path,
                                                        monkeypatch):
    path = _mk_table(tmp_path, monkeypatch, {"bn": 7})
    assert autotune.lookup("qlinear", QDIMS) == {}
    problems = autotune.validate_table(path)
    assert len(problems) == 1 and "illegal params" in problems[0]


def test_load_table_missing_and_reload_on_save(tmp_path):
    path = tmp_path / "TUNE_x.json"
    assert autotune.load_table(path) == {}
    t1 = {"version": 1, "configs": {}}
    autotune.save_table(t1, path)
    assert autotune.load_table(path) == t1
    t2 = {"version": 1, "configs": {"cpu-interpret": {}}}
    autotune.save_table(t2, path)
    assert autotune.load_table(path) == t2
    path.write_text("{not json")
    assert autotune.load_table(path) == {}


def test_validate_table_structural_problems(tmp_path):
    assert autotune.validate_table(tmp_path / "absent.json") == []
    path = tmp_path / "TUNE_bad.json"
    path.write_text("{not json")
    assert len(autotune.validate_table(path)) == 1
    bad = {"version": 99, "configs": {"cpu-interpret": {
        "nope": {"m=8": {"params": {}}},
        "qlinear": {
            "m=x": {"params": {}},                     # unparseable key
            "m=8": {"params": {}},                     # wrong dims
            "e=1,m=8,k=256,n=256": {"params": {"bn": 7}},  # illegal
        }}}}
    path.write_text(json.dumps(bad))
    problems = autotune.validate_table(path)
    assert len(problems) == 5
    joined = "\n".join(problems)
    for frag in ("version", "unknown kernel", "bad shape key", "dims !=",
                 "illegal params"):
        assert frag in joined, frag


# ---------------------------------------------------------------------------
# Dispatch wiring: an override changes the tiling, never the bits
# ---------------------------------------------------------------------------

def test_override_retiles_qlinear_dispatch_bitwise():
    rng = np.random.default_rng(3)
    k, n = 256, 256
    tw = make_ternary_weight(
        jnp.asarray(rng.standard_normal((k, n)), jnp.float32) * 0.02)
    sc = jnp.asarray(tw.scale).reshape(1, 1)
    x = jnp.asarray(rng.standard_normal((5, k)), jnp.float32)
    base = ops.qlinear_fused(x, tw.packed, sc, impl="pallas")
    with autotune.override("qlinear", bm=8, bn=256, bkq=128, eg=1):
        tuned = ops.qlinear_fused(x, tw.packed, sc, impl="pallas")
    assert (np.asarray(base) == np.asarray(tuned)).all()


def test_override_retiles_ternary_matmul_dispatch_bitwise():
    rng = np.random.default_rng(4)
    k, n = 64, 64
    tw = make_ternary_weight(
        jnp.asarray(rng.standard_normal((k, n)), jnp.float32) * 0.02)
    xq = jnp.asarray(rng.integers(-127, 128, (8, k)), jnp.int8)
    base = ops.ternary_matmul(xq, tw, impl="pallas")
    with autotune.override("ternary_matmul", bm=8, bk=32, bn=32):
        tuned = ops.ternary_matmul(xq, tw, impl="pallas")
    ref = ops.ternary_matmul(xq, tw, impl="ref")
    assert (np.asarray(base) == np.asarray(tuned)).all()
    assert (np.asarray(tuned) == np.asarray(ref)).all()


# ---------------------------------------------------------------------------
# Log-and-sweep sidecar: dispatch shapes -> JSON -> --from-log sweep set
# ---------------------------------------------------------------------------

def test_shape_log_records_dedupes_and_loads(tmp_path):
    path = tmp_path / "shapes.json"
    autotune.start_shape_log(path)
    try:
        dims = {"m": 8, "k": 64, "n": 128}
        autotune.observe("ternary_matmul", dims)
        autotune.observe("ternary_matmul", dims)          # dedup'd
        autotune.observe("qlinear", {"e": 1, "m": 4, "k": 64, "n": 64})
        autotune.observe("not_a_kernel", {"m": 1})        # unknown: no-op
    finally:
        autotune.stop_shape_log()
    raw = json.loads(path.read_text())
    assert raw["version"] == autotune.SHAPE_LOG_VERSION
    assert raw["shapes"]["ternary_matmul"] == [
        autotune.shape_key("ternary_matmul", dims)]
    loaded = autotune.load_shape_log(path)
    assert loaded == {"ternary_matmul": [dims],
                      "qlinear": [{"e": 1, "m": 4, "k": 64, "n": 64}]}


def test_shape_log_survives_restart_and_unions(tmp_path):
    """A second enable (fresh ``seen`` set, e.g. a new server process)
    appends to the same sidecar instead of clobbering it."""
    path = tmp_path / "shapes.json"
    autotune.start_shape_log(path)
    autotune.observe("ternary_matmul", {"m": 8, "k": 64, "n": 128})
    autotune.stop_shape_log()
    autotune.start_shape_log(path)
    autotune.observe("ternary_matmul", {"m": 8, "k": 64, "n": 128})
    autotune.observe("ternary_matmul", {"m": 16, "k": 64, "n": 128})
    autotune.stop_shape_log()
    loaded = autotune.load_shape_log(path)
    assert len(loaded["ternary_matmul"]) == 2
    assert {"m": 8, "k": 64, "n": 128} in loaded["ternary_matmul"]
    assert {"m": 16, "k": 64, "n": 128} in loaded["ternary_matmul"]


def test_merged_shapes_grows_defaults_without_duplicates(tmp_path):
    path = tmp_path / "shapes.json"
    autotune.start_shape_log(path)
    known = autotune.DEFAULT_SHAPES["ternary_matmul"][0]
    novel = {"m": 3, "k": 64, "n": 128}
    assert novel not in autotune.DEFAULT_SHAPES["ternary_matmul"]
    autotune.observe("ternary_matmul", known)             # already swept
    autotune.observe("ternary_matmul", novel)
    autotune.stop_shape_log()
    merged = autotune.merged_shapes(path)
    base = autotune.DEFAULT_SHAPES["ternary_matmul"]
    assert merged["ternary_matmul"][:len(base)] == base
    assert merged["ternary_matmul"].count(known) == 1
    assert novel in merged["ternary_matmul"]


def test_shape_log_env_off_by_default(tmp_path, monkeypatch):
    monkeypatch.delenv(autotune.SHAPE_LOG_ENV, raising=False)
    autotune.stop_shape_log()
    assert autotune.shape_log_path() is None
    monkeypatch.setenv(autotune.SHAPE_LOG_ENV, "0")
    assert autotune.shape_log_path() is None
    monkeypatch.setenv(autotune.SHAPE_LOG_ENV, str(tmp_path / "s.json"))
    assert autotune.shape_log_path() == tmp_path / "s.json"


def test_ops_dispatch_feeds_the_shape_log(tmp_path):
    """A real kernel call while logging is armed lands its dims in the
    sidecar — the PooledEngine(shape_log=...) wiring minus the engine."""
    path = tmp_path / "shapes.json"
    rng = np.random.default_rng(5)
    k, n = 64, 64
    tw = make_ternary_weight(
        jnp.asarray(rng.standard_normal((k, n)), jnp.float32) * 0.02)
    xq = jnp.asarray(rng.integers(-127, 128, (8, k)), jnp.int8)
    autotune.start_shape_log(path)
    try:
        ops.ternary_matmul(xq, tw)
    finally:
        autotune.stop_shape_log()
    loaded = autotune.load_shape_log(path)
    assert {"m": 8, "k": k, "n": n} in loaded["ternary_matmul"]
