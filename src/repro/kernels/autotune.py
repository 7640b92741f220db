"""Tile autotuning for the fused Pallas kernels (DESIGN.md §Autotuning).

Every kernel entry in :mod:`repro.kernels.ops` carries hardcoded block
shapes chosen by hand. This module makes them *swept*: per kernel and
per workload shape it generates tiling candidates from the roofline
bounds in :mod:`repro.analysis.roofline` (resident-VMEM budget +
arithmetic-intensity ranking), times each candidate on synthetic
operands, and persists the winner to a config-keyed ``TUNE_*.json``
table that ``ops.py`` consults at dispatch time.

Sweep space per kernel (every axis is a pure tiling knob — any legal
setting is bitwise the default, pinned by the kernel test matrix):

  ================  ==========================================
  kernel            swept parameters
  ================  ==========================================
  ternary_matmul    bm, bk, bn
  qlinear           bm, bn, bkq (two-pass k-tiled barrier),
                    eg (experts per grid step)
  ffn               bm, bf, bn, bkq
  prefill           block (kv tile), bq (query-row tile)
  decode            n_slots (candidate K/V buffers, 1 or 2)
  ================  ==========================================

Precedence at dispatch (``lookup``):

  1. an active :func:`override` context (tests / experiment flags);
  2. the tuning table — ``REPRO_TUNE_TABLE`` path env or the repo-root
     ``TUNE_kernels.json`` — under the current config key, entries
     validated against the workload's divisibility constraints;
  3. ``{}`` — the caller's hardcoded defaults. With no table on disk
     (and ``REPRO_TUNE=0`` forces this) dispatch is bitwise the
     pre-autotune code path.

The module is imported by ``ops.py`` at module scope, so everything here
stays import-light: jax / kernel modules load lazily inside the sweep
functions only.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from pathlib import Path

from repro.analysis.roofline import (arithmetic_intensity, machine_balance,
                                     vmem_budget)

ROOT = Path(__file__).resolve().parents[3]
DEFAULT_TABLE = ROOT / "TUNE_kernels.json"
TABLE_VERSION = 1
DEFAULT_SHAPE_LOG = ROOT / "TUNE_shapes.json"
SHAPE_LOG_ENV = "REPRO_SHAPE_LOG"
SHAPE_LOG_VERSION = 1

KERNELS = ("ternary_matmul", "qlinear", "ffn", "prefill", "decode")

# dims each kernel's shape key is built from, and the params it sweeps
KERNEL_DIMS = {
    "ternary_matmul": ("m", "k", "n"),
    "qlinear": ("e", "m", "k", "n"),
    "ffn": ("e", "m", "k", "f", "n"),
    "prefill": ("bhg", "r", "d", "m", "chunk"),
    "decode": ("bhg", "g", "d", "m", "block", "k_keep"),
}
KERNEL_PARAMS = {
    "ternary_matmul": ("bm", "bk", "bn"),
    "qlinear": ("bm", "bn", "bkq", "eg"),
    "ffn": ("bm", "bf", "bn", "bkq"),
    "prefill": ("block", "bq"),
    "decode": ("n_slots",),
}


# ---------------------------------------------------------------------------
# Table I/O and dispatch lookup
# ---------------------------------------------------------------------------

_OVERRIDES: dict[str, dict] = {}
_CACHE: dict[str, tuple[float, dict]] = {}


def table_path() -> Path:
    return Path(os.environ.get("REPRO_TUNE_TABLE", DEFAULT_TABLE))


def config_key() -> str:
    """Backend the timings were taken on — a cpu-interpret sweep must not
    steer a real-TPU dispatch and vice versa."""
    import jax
    backend = jax.default_backend()
    return backend if backend == "tpu" else f"{backend}-interpret"


def shape_key(kernel: str, dims: dict) -> str:
    names = KERNEL_DIMS[kernel]
    assert set(dims) == set(names), (kernel, dims)
    return ",".join(f"{k}={int(dims[k])}" for k in names)


def load_table(path: str | Path | None = None) -> dict:
    """Parse the table (``{}`` when absent/unreadable), mtime-cached."""
    p = Path(path) if path is not None else table_path()
    try:
        mtime = p.stat().st_mtime
    except OSError:
        return {}
    key = str(p)
    cached = _CACHE.get(key)
    if cached and cached[0] == mtime:
        return cached[1]
    try:
        table = json.loads(p.read_text())
    except (OSError, ValueError):
        return {}
    if not isinstance(table, dict):
        table = {}
    _CACHE[key] = (mtime, table)
    return table


def save_table(table: dict, path: str | Path | None = None) -> Path:
    p = Path(path) if path is not None else table_path()
    p.write_text(json.dumps(table, indent=2, sort_keys=True) + "\n")
    _CACHE.pop(str(p), None)
    return p


@contextlib.contextmanager
def override(kernel: str, **params):
    """Force ``params`` for every ``lookup(kernel, ...)`` in the block —
    the flag override of the precedence chain (beats the table)."""
    assert kernel in KERNELS, kernel
    prev = _OVERRIDES.get(kernel)
    _OVERRIDES[kernel] = dict(params)
    try:
        yield
    finally:
        if prev is None:
            _OVERRIDES.pop(kernel, None)
        else:
            _OVERRIDES[kernel] = prev


def valid_params(kernel: str, dims: dict, params: dict) -> bool:
    """Divisibility / legality screen for a (possibly stale) table entry."""
    if not isinstance(params, dict):
        return False
    if not set(params) <= set(KERNEL_PARAMS[kernel]):
        return False
    try:
        p = {k: int(v) for k, v in params.items()}
    except (TypeError, ValueError):
        return False
    d = dims
    if kernel == "ternary_matmul":
        return (p.get("bm", 8) >= 1
                and d["k"] % p.get("bk", d["k"]) == 0
                and d["n"] % p.get("bn", d["n"]) == 0)
    if kernel == "qlinear":
        bkq = p.get("bkq", 0)
        return (p.get("bm", 8) % 8 == 0 and p.get("bm", 8) >= 8
                and _lane_block(d["n"], p.get("bn", d["n"]))
                and (bkq == 0 or _lane_block(d["k"], bkq))
                and d["e"] % p.get("eg", 1) == 0)
    if kernel == "ffn":
        bkq = p.get("bkq", 0)
        return (p.get("bm", 8) % 8 == 0 and p.get("bm", 8) >= 8
                and _lane_block(d["f"], p.get("bf", d["f"]))
                and _lane_block(d["n"], p.get("bn", d["n"]))
                and (bkq == 0 or _lane_block(d["k"], bkq)))
    if kernel == "prefill":
        block = p.get("block", 0)
        bq = p.get("bq", 0)
        # the wrapper pads M up to `block`, so any block ≥ 1 is legal; a
        # query-row tile is a sublane block: a multiple of 8 or all R rows
        return block >= 1 and (bq == 0 or (
            d["r"] % bq == 0 and (bq % 8 == 0 or bq == d["r"])))
    if kernel == "decode":
        # Mosaic pipelines one or two buffers per input, no deeper
        return 1 <= p.get("n_slots", 2) <= 2
    return False


def _lane_block(n: int, b: int) -> bool:
    """A column block Mosaic takes: divides n and is a multiple of 128
    lanes or the whole dim."""
    return b >= 1 and n % b == 0 and (b % 128 == 0 or b == n)


def lookup(kernel: str, dims: dict) -> dict:
    """Tuned params for this kernel+shape, or ``{}`` (use the defaults).

    Checked in precedence order: override context → table entry (env
    ``REPRO_TUNE=0`` disables this leg) → ``{}``. Invalid/stale entries
    fall through to ``{}`` rather than crash dispatch.
    """
    ov = _OVERRIDES.get(kernel)
    if ov is not None:
        return dict(ov) if valid_params(kernel, dims, ov) else {}
    if os.environ.get("REPRO_TUNE", "1") == "0":
        return {}
    table = load_table()
    if not table:
        return {}
    entry = (table.get("configs", {}).get(config_key(), {})
             .get(kernel, {}).get(shape_key(kernel, dims)))
    if not isinstance(entry, dict):
        return {}
    params = entry.get("params", {})
    return dict(params) if valid_params(kernel, dims, params) else {}


# ---------------------------------------------------------------------------
# Shape log (log-and-sweep, DESIGN.md §Autotuning): the serving engine
# records every distinct kernel dispatch shape to a JSON sidecar, and a
# later sweep (on the real hardware) reads it back so the swept-shape
# set grows from the shapes production traffic actually dispatches —
# not just the DEFAULT_SHAPES guesses.
# ---------------------------------------------------------------------------

_SHAPE_LOG: dict = {"path": None, "seen": set()}


def shape_log_path() -> Path | None:
    """Active sidecar path: explicit ``start_shape_log`` wins, then the
    ``REPRO_SHAPE_LOG`` env (its value = the path, or ``1`` for the
    repo-root default). ``None`` = logging off (the default: dispatch
    must not grow file I/O unless asked)."""
    if _SHAPE_LOG["path"] is not None:
        return _SHAPE_LOG["path"]
    env = os.environ.get(SHAPE_LOG_ENV)
    if not env or env == "0":
        return None
    return DEFAULT_SHAPE_LOG if env == "1" else Path(env)


def start_shape_log(path: str | Path | None = None) -> Path:
    """Enable shape logging (e.g. ``PooledEngine(shape_log=...)``)."""
    p = Path(path) if path is not None else DEFAULT_SHAPE_LOG
    _SHAPE_LOG["path"] = p
    _SHAPE_LOG["seen"] = set()
    return p


def stop_shape_log() -> None:
    _SHAPE_LOG["path"] = None
    _SHAPE_LOG["seen"] = set()


def observe(kernel: str, dims: dict) -> None:
    """Record one dispatch shape to the sidecar (dedup'd, write-through).

    Called by every ``ops.py`` entry point at trace time — shapes are
    static Python ints, so a shape is observed once per compile, not per
    step; the in-memory ``seen`` set makes repeat traces free and the
    read-modify-write below keeps the file a union across processes.
    No-op unless logging is enabled.
    """
    p = shape_log_path()
    if p is None or kernel not in KERNEL_DIMS:
        return
    key = (str(p), kernel, shape_key(kernel, dims))
    if key in _SHAPE_LOG["seen"]:
        return
    _SHAPE_LOG["seen"].add(key)
    try:
        log = json.loads(p.read_text())
        assert isinstance(log, dict)
    except (OSError, ValueError, AssertionError):
        log = {}
    log.setdefault("version", SHAPE_LOG_VERSION)
    shapes = log.setdefault("shapes", {}).setdefault(kernel, [])
    skey = shape_key(kernel, dims)
    if skey not in shapes:
        shapes.append(skey)
        shapes.sort()
    p.write_text(json.dumps(log, indent=2, sort_keys=True) + "\n")


def load_shape_log(path: str | Path | None = None) -> dict:
    """Sidecar → ``{kernel: [dims, ...]}`` (malformed entries dropped)."""
    p = Path(path) if path is not None else (shape_log_path()
                                             or DEFAULT_SHAPE_LOG)
    try:
        log = json.loads(p.read_text())
    except (OSError, ValueError):
        return {}
    out: dict[str, list[dict]] = {}
    for kernel, skeys in (log.get("shapes") or {}).items():
        if kernel not in KERNELS or not isinstance(skeys, list):
            continue
        for skey in skeys:
            try:
                dims = {k: int(v) for k, v in
                        (kv.split("=") for kv in skey.split(","))}
            except (ValueError, AttributeError):
                continue
            if set(dims) != set(KERNEL_DIMS[kernel]):
                continue
            out.setdefault(kernel, []).append(dims)
    return out


def merged_shapes(path: str | Path | None = None) -> dict:
    """DEFAULT_SHAPES grown by the sidecar's logged shapes (dedup'd) —
    the sweep set of ``--from-log``."""
    out = {k: [dict(d) for d in v] for k, v in DEFAULT_SHAPES.items()}
    for kernel, shapes in load_shape_log(path).items():
        for dims in shapes:
            if dims not in out.setdefault(kernel, []):
                out[kernel].append(dims)
    return out


def validate_table(path: str | Path | None = None) -> list[str]:
    """Structural check for the CI gate: every entry must parse, name a
    known kernel, carry a well-formed shape key, and pass the legality
    screen against its own dims. Returns problem strings (empty = OK;
    a missing table is OK — the fallback is the contract)."""
    p = Path(path) if path is not None else table_path()
    if not p.exists():
        return []
    try:
        table = json.loads(p.read_text())
    except (OSError, ValueError) as e:
        return [f"{p}: unparseable ({e})"]
    problems = []
    if table.get("version") != TABLE_VERSION:
        problems.append(f"{p}: version {table.get('version')!r} "
                        f"!= {TABLE_VERSION}")
    for cfg, kernels in table.get("configs", {}).items():
        for kernel, entries in kernels.items():
            if kernel not in KERNELS:
                problems.append(f"{cfg}: unknown kernel {kernel!r}")
                continue
            for skey, entry in entries.items():
                try:
                    dims = {k: int(v) for k, v in
                            (kv.split("=") for kv in skey.split(","))}
                except ValueError:
                    problems.append(f"{cfg}/{kernel}: bad shape key {skey!r}")
                    continue
                if set(dims) != set(KERNEL_DIMS[kernel]):
                    problems.append(f"{cfg}/{kernel}: {skey!r} dims != "
                                    f"{KERNEL_DIMS[kernel]}")
                    continue
                if not valid_params(kernel, dims, entry.get("params")):
                    problems.append(
                        f"{cfg}/{kernel}/{skey}: illegal params "
                        f"{entry.get('params')!r}")
    return problems


# ---------------------------------------------------------------------------
# Candidate generation from the roofline bounds
# ---------------------------------------------------------------------------

def _divisors(n: int, lo: int = 1, hi: int | None = None) -> list[int]:
    hi = n if hi is None else min(hi, n)
    return [d for d in range(lo, hi + 1) if n % d == 0]


def _pow2_range(lo: int, hi: int) -> list[int]:
    out, v = [], lo
    while v <= hi:
        out.append(v)
        v *= 2
    return out


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _tile_footprint(kernel: str, dims: dict, p: dict) -> int:
    """Resident VMEM bytes of one grid step (inputs + scratch + output).

    An estimate, not a Mosaic allocation — the point is to *rank and
    prune* candidates against :func:`repro.analysis.roofline.vmem_budget`
    before spending a compile on them.
    """
    d = dims
    if kernel == "ternary_matmul":
        bm, bk, bn = p["bm"], p["bk"], p["bn"]
        return bm * bk + bk // 4 * bn + 2 * bm * bn * 4
    if kernel == "qlinear":
        bm, bn, bkq, eg = p["bm"], p["bn"], p["bkq"], p["eg"]
        k = d["k"]
        x_tile = eg * bm * (bkq if bkq else k) * 4
        scratch = eg * bm * k + eg * bm * 8 + (eg * bm * 4 if bkq else 0)
        return (x_tile + eg * (k // 4) * bn + eg * bn * 4
                + eg * bm * bn * 4 + scratch)
    if kernel == "ffn":
        bm, bf, bn, bkq = p["bm"], p["bf"], p["bn"], p["bkq"]
        k, f = d["k"], d["f"]
        x_tile = bm * (bkq if bkq else k) * 4
        scratch = bm * k + bm * 8 + bm * f * 5 + (bm * 4 if bkq else 0)
        return (x_tile + 2 * (k // 4) * bf + f // 4 * bn
                + bm * bn * 4 + scratch)
    if kernel == "prefill":
        block = p["block"]
        bq = p["bq"] or d["r"]
        dh = d["d"]
        q_tiles = bq * dh + bq * 4                        # int8 q + f32 scale
        kv_tiles = 2 * block * dh + 2 * block * 4
        scratch = bq * 128 * 4 * 2 + bq * dh * 4
        return q_tiles + kv_tiles + bq * dh * 4 + scratch
    if kernel == "decode":
        ns = p["n_slots"]
        g, dh, block, m = d["g"], d["d"], d["block"], d["m"]
        nbp = _round_up(m // block, 128)
        slots = ns * (2 * block * dh + 2 * block * 4)
        scratch = 2 * g * nbp * 4 + g * 128 * 4 * 2 + g * dh * 4
        return g * dh + g * 4 + block * dh // 2 + slots + scratch
    raise ValueError(kernel)


def _tile_intensity(kernel: str, dims: dict, p: dict) -> float:
    """Arithmetic intensity of one grid step: MXU FLOPs over the HBM bytes
    the step's input windows stream in (output + resident scratch are
    amortized). Ranks candidates toward the roofline ridge."""
    d = dims
    if kernel == "ternary_matmul":
        bm, bk, bn = p["bm"], p["bk"], p["bn"]
        return arithmetic_intensity(2 * bm * bk * bn,
                                    bm * bk + bk // 4 * bn)
    if kernel == "qlinear":
        bm, bn, bkq, eg = p["bm"], p["bn"], p["bkq"], p["eg"]
        k = d["k"]
        flops = 2 * eg * bm * k * bn
        x_bytes = eg * bm * (bkq if bkq else k) * 4
        return arithmetic_intensity(flops, x_bytes + eg * (k // 4) * bn)
    if kernel == "ffn":
        bm, bf, bn, bkq = p["bm"], p["bf"], p["bn"], p["bkq"]
        k, f = d["k"], d["f"]
        flops = 2 * bm * k * bf * 2 + 2 * bm * f * bn
        x_bytes = bm * (bkq if bkq else k) * 4
        return arithmetic_intensity(flops, x_bytes + 2 * (k // 4) * bf
                                    + f // 4 * bn)
    if kernel == "prefill":
        block = p["block"]
        bq = p["bq"] or d["r"]
        dh = d["d"]
        flops = 2 * bq * block * dh * 2
        return arithmetic_intensity(flops, bq * dh + 2 * block * dh)
    if kernel == "decode":
        g, dh, block = d["g"], d["d"], d["block"]
        flops = 2 * g * block * dh * 2
        # deeper pipelines hide latency, not bytes; nudge the rank so the
        # sweep tries them in order
        return arithmetic_intensity(flops, 2 * block * dh) + p["n_slots"]
    raise ValueError(kernel)


def candidates(kernel: str, dims: dict, *,
               max_candidates: int = 12) -> list[dict]:
    """Legal tiling candidates, VMEM-pruned, AI-ranked (best first).

    The hardcoded default shape is always candidate 0 so a sweep can
    never regress dispatch below the status quo.
    """
    d = dims
    raw: list[dict] = []
    if kernel == "ternary_matmul":
        for bm in _pow2_range(8, min(256, _round_up(max(d["m"], 1), 8))):
            for bk in _divisors(d["k"], 32, 1024):
                for bn in _divisors(d["n"], 32, 512):
                    raw.append({"bm": bm, "bk": bk, "bn": bn})
        default = {"bm": min(128, _round_up(max(d["m"], 1), 8)),
                   "bk": min(512, d["k"]), "bn": min(128, d["n"])}
    elif kernel == "qlinear":
        for bm in _pow2_range(8, min(256, _round_up(max(d["m"], 1), 8))):
            for bn in _divisors(d["n"], 32, 512):
                for bkq in [0] + _divisors(d["k"], 128, 1024):
                    for eg in _divisors(d["e"], 1, 8):
                        raw.append({"bm": bm, "bn": bn, "bkq": bkq,
                                    "eg": eg})
        default = {"bm": min(128, _round_up(max(d["m"], 1), 8)),
                   "bn": _fallback_block(d["n"], d["k"] // 4), "bkq": 0,
                   "eg": 1}
    elif kernel == "ffn":
        for bm in _pow2_range(8, min(256, _round_up(max(d["m"], 1), 8))):
            for bf in _divisors(d["f"], 32, 512):
                for bn in _divisors(d["n"], 32, 512):
                    for bkq in [0] + _divisors(d["k"], 128, 1024):
                        raw.append({"bm": bm, "bf": bf, "bn": bn,
                                    "bkq": bkq})
        default = {"bm": min(128, _round_up(max(d["m"], 1), 8)),
                   "bf": _fallback_block(d["f"], None),     # gated
                   "bn": _fallback_block(d["n"], d["f"] // 4), "bkq": 0}
    elif kernel == "prefill":
        for block in _pow2_range(32, 512):
            for bq in [0] + _divisors(d["r"], 8, d["r"]):
                raw.append({"block": block, "bq": bq})
        default = {"block": min(128, d["m"]), "bq": 0}
    elif kernel == "decode":
        raw = [{"n_slots": ns} for ns in (1, 2)]
        default = {"n_slots": 2}
    else:
        raise ValueError(kernel)

    budget = vmem_budget()
    legal = [p for p in raw
             if valid_params(kernel, d, p)
             and _tile_footprint(kernel, d, p) <= budget]
    legal.sort(key=lambda p: _tile_intensity(kernel, d, p), reverse=True)
    out = [default] if valid_params(kernel, d, default) else []
    for p in legal:
        if p not in out:
            out.append(p)
        if len(out) >= max_candidates:
            break
    return out


def pick_block(n: int, col_bytes: int | None, target: int = 128) -> int:
    """Default column block for a weight of n columns, ``col_bytes``
    VMEM bytes each: n itself when it fits one ``target`` block (one MXU
    lane width), else ``target`` where it divides n. Mosaic takes a lane
    block only as a multiple of 128 or the whole dim, so any other n runs
    as one whole-dim block if its double-buffered weight block fits the
    VMEM budget. Where it does not, or where the n columns are not the
    whole array (``col_bytes`` None), no legal block exists and this
    raises (``quantize_params`` pads the FFN hidden width so it never
    does)."""
    if n <= target:
        return n
    if n % target == 0:
        return target
    if col_bytes is not None and 2 * n * col_bytes <= vmem_budget():
        return n
    raise ValueError(f"no legal column block for n={n}: pad it to a "
                     f"multiple of {target}")


def _fallback_block(n: int, col_bytes: int | None) -> int | None:
    """pick_block, or None where it refuses n (the default candidate then
    fails the screen)."""
    try:
        return pick_block(n, col_bytes)
    except ValueError:
        return None


# ---------------------------------------------------------------------------
# The sweep: time candidates on synthetic operands, keep the winner
# ---------------------------------------------------------------------------

def _bench(fn, repeats: int) -> float:
    """Median wall-µs of ``fn`` (one untimed warmup absorbs the compile)."""
    import jax
    jax.block_until_ready(fn())
    samples = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        samples.append((time.perf_counter() - t0) * 1e6)
    samples.sort()
    return samples[len(samples) // 2]


def _make_runner(kernel: str, dims: dict):
    """Synthetic operands for one workload shape → ``run(params) -> fn``."""
    import importlib
    import numpy as np
    import jax.numpy as jnp
    # the package __init__ re-exports ops wrappers under the same names,
    # shadowing the submodule attributes — bind the modules explicitly
    _dec = importlib.import_module("repro.kernels.decode_attention")
    _pf = importlib.import_module("repro.kernels.prefill_attention")
    _ql = importlib.import_module("repro.kernels.qlinear")
    _tmm = importlib.import_module("repro.kernels.ternary_matmul")

    interpret = _interpret()
    rng = np.random.default_rng(0)
    d = dims

    def i8(*shape):
        return jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)

    def u8(*shape):
        return jnp.asarray(rng.integers(0, 256, size=shape), jnp.uint8)

    def f32(*shape, lo=0.01, hi=0.1):
        return jnp.asarray(rng.uniform(lo, hi, size=shape), jnp.float32)

    if kernel == "ternary_matmul":
        x = i8(_round_up(max(d["m"], 1), 8), d["k"])
        wp = u8(d["k"] // 4, d["n"])
        return lambda p: lambda: _tmm.ternary_matmul(
            x, wp, d["k"], bm=min(p["bm"], x.shape[0]), bk=p["bk"],
            bn=p["bn"], interpret=interpret)
    if kernel == "qlinear":
        m = _round_up(max(d["m"], 1), 8)
        x = f32(d["e"], m, d["k"], lo=-1.0, hi=1.0)
        wp = u8(d["e"], d["k"] // 4, d["n"])
        sc = f32(d["e"], 1, d["n"])
        return lambda p: lambda: _ql.fused_qlinear(
            x, wp, sc, None, bm=min(p["bm"], m), bn=p["bn"], bkq=p["bkq"],
            eg=p["eg"], act="silu", interpret=interpret)
    if kernel == "ffn":
        m = _round_up(max(d["m"], 1), 8)
        x = f32(d["e"], m, d["k"], lo=-1.0, hi=1.0)
        gup = u8(d["e"], d["k"] // 4, 2 * d["f"])
        gus = f32(d["e"], 1, 2 * d["f"])
        dp = u8(d["e"], d["f"] // 4, d["n"])
        ds = f32(d["e"], 1, d["n"])
        return lambda p: lambda: _ql.fused_ffn(
            x, gup, gus, dp, ds, bm=min(p["bm"], m), bf=p["bf"], bn=p["bn"],
            bkq=p["bkq"], act="silu", gated=True, interpret=interpret)
    if kernel == "prefill":
        qi = i8(d["bhg"], d["r"], d["d"])
        qsc = f32(d["bhg"], d["r"], 1)
        kv_len = jnp.full((d["bhg"],), d["m"], jnp.int32)
        po = jnp.zeros((1,), jnp.int32)

        def run(p):
            m = _round_up(d["m"], p["block"])
            kc, vc = i8(d["bhg"], m, d["d"]), i8(d["bhg"], m, d["d"])
            ks, vs = f32(d["bhg"], m, 1), f32(d["bhg"], m, 1)
            return lambda: _pf.fused_prefill_attention(
                qi, qsc, kc, vc, ks, vs, kv_len, po, hkv=1,
                chunk=d["chunk"], block=p["block"], bq=p["bq"], causal=True,
                window=0, softmax_scale=d["d"] ** -0.5, interpret=interpret)
        return run
    if kernel == "decode":
        qi = i8(d["bhg"], d["g"], d["d"])
        qsc = f32(d["bhg"], d["g"], 1)
        kc, vc = i8(d["bhg"], d["m"], d["d"]), i8(d["bhg"], d["m"], d["d"])
        ks, vs = f32(d["bhg"], d["m"], 1), f32(d["bhg"], d["m"], 1)
        feat = u8(d["bhg"], d["m"], d["d"] // 2)
        nl = jnp.full((d["bhg"],), d["m"], jnp.int32)
        po = jnp.zeros((1,), jnp.int32)
        return lambda p: lambda: _dec.fused_decode_attention(
            qi, qsc, kc, vc, ks, vs, feat, nl, po, hkv=1, block=d["block"],
            k_keep=d["k_keep"], window=0, softmax_scale=d["d"] ** -0.5,
            n_slots=p["n_slots"], interpret=interpret)
    raise ValueError(kernel)


def _interpret() -> bool:
    import jax
    return jax.default_backend() != "tpu"


def sweep_kernel(kernel: str, dims: dict, *, repeats: int = 3,
                 max_candidates: int = 12, log=None) -> dict:
    """Time the candidate set for one shape; return its table entry."""
    runner = _make_runner(kernel, dims)
    best = None
    for p in candidates(kernel, dims, max_candidates=max_candidates):
        us = _bench(runner(p), repeats)
        if log:
            log(f"  {kernel} {shape_key(kernel, dims)} {p} -> {us:.1f}us")
        if best is None or us < best["us"]:
            best = {"params": p, "us": round(us, 1)}
    return best


# serving-ish workload shapes swept by default (small enough for
# interpret mode; a TPU run sweeps the same keys under its own config)
DEFAULT_SHAPES: dict[str, list[dict]] = {
    "ternary_matmul": [{"m": 8, "k": 256, "n": 256}],
    "qlinear": [{"e": 1, "m": 8, "k": 256, "n": 256}],
    "ffn": [{"e": 1, "m": 8, "k": 256, "f": 512, "n": 256}],
    "prefill": [{"bhg": 2, "r": 64, "d": 64, "m": 256, "chunk": 32}],
    "decode": [{"bhg": 2, "g": 2, "d": 64, "m": 256, "block": 64,
                "k_keep": 2}],
}


def run_sweep(kernels=None, shapes=None, *, out_path=None, repeats: int = 3,
              max_candidates: int = 12, log=print) -> dict:
    """Sweep and merge winners into the table (other configs preserved)."""
    kernels = list(kernels or KERNELS)
    shapes = shapes or DEFAULT_SHAPES
    path = Path(out_path) if out_path is not None else table_path()
    table = load_table(path) or {}
    table.setdefault("version", TABLE_VERSION)
    cfg = table.setdefault("configs", {}).setdefault(config_key(), {})
    for kernel in kernels:
        for dims in shapes.get(kernel, []):
            entry = sweep_kernel(kernel, dims, repeats=repeats,
                                 max_candidates=max_candidates, log=log)
            if entry is not None:
                cfg.setdefault(kernel, {})[shape_key(kernel, dims)] = entry
                if log:
                    log(f"{kernel} {shape_key(kernel, dims)}: "
                        f"best {entry['params']} ({entry['us']}us)")
    save_table(table, path)
    return table


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--kernel", action="append", choices=KERNELS,
                    help="kernel(s) to sweep (default: all)")
    ap.add_argument("--out", default=None, help="table path "
                    "(default: REPRO_TUNE_TABLE or TUNE_kernels.json)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--max-candidates", type=int, default=12)
    ap.add_argument("--from-log", nargs="?", const=True, default=None,
                    metavar="PATH",
                    help="grow the swept-shape set from a serving shape "
                         "log (PooledEngine shape_log= / REPRO_SHAPE_LOG "
                         "sidecar; default path TUNE_shapes.json)")
    ap.add_argument("--check", action="store_true",
                    help="validate the table instead of sweeping")
    args = ap.parse_args(argv)
    if args.check:
        problems = validate_table(args.out)
        for p in problems:
            print(f"autotune: {p}", file=sys.stderr)
        print(f"autotune: table "
              f"{'INVALID' if problems else 'OK'} ({table_path()})")
        return 1 if problems else 0
    shapes = None
    if args.from_log is not None:
        log_path = None if args.from_log is True else args.from_log
        shapes = merged_shapes(log_path)
        n_logged = sum(len(v) for v in load_shape_log(log_path).values())
        print(f"autotune: sweeping {n_logged} logged serving shape(s) "
              f"on top of the defaults")
    run_sweep(args.kernel, shapes, out_path=args.out, repeats=args.repeats,
              max_candidates=args.max_candidates)
    return 0


if __name__ == "__main__":
    sys.exit(main())
