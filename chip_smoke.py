#!/usr/bin/env python3
"""Smoke test of the serving path on one TPU chip.

    python3 chip_smoke.py [--seed N]

One process drives the served path of bitnet-3b at its published widths
and full depth (26 layers), with random weights made from ``--seed``:

  a. refuse anything but a TPU and the Pallas kernels (no CPU fallback);
  b. kernel parity at full width: fused QKV ``qlinear``, the fused FFN,
     decode attention with and without the LOP screen, and prefill
     attention, each through Pallas and through its ``kernels/ref.py``
     oracle on the same seeded inputs;
  c. build the scheduler with ``launch/server.build_scheduler`` (4 slots,
     ``max_len`` 2048) and serve it over HTTP on a loopback port;
  d. send 4 concurrent greedy requests (one streamed) from client
     threads and check every token against ``lockstep_generate``;
  e. check that the compiled decode step contains the kernels;
  f. print what the run cost, as smoke information (not metrics).

The last line of stdout is one JSON object, ``{"ok": true, "device":
{...}}``, printed only when every phase passed; any failure exits
non-zero. The compile cache follows ``repro.launch.compile_cache``.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import threading
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Parity tolerances, relative to the largest |reference| output:
# - projections: the barrier divides and rounds on the VPU in both arms;
#   a rare rounding flip of one int8 activation moves an output by one
#   quantization step (~1e-3 of the largest output). With integer inputs
#   whose row absmax is 127 the barrier is exact, and then the fused
#   projection must equal the oracle bit for bit (the int32 accumulator
#   times γ = 1).
# - attention: f32 online softmax against the oracle at matmul
#   precision "highest"; exp and summation order differ by ulps.
PROJ_RTOL = 1e-3
ATTN_RTOL = 2e-5

N_REQUESTS = 4
PROMPT_LENS = (64, 300, 700, 1024)     # 300+ tokens span > k_keep blocks
MAX_TOKENS = 32
STREAMED = 2                           # index of the streamed request


def info(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# (a) the device and the kernel arm
# ---------------------------------------------------------------------------

def require_tpu():
    impl = os.environ.get("REPRO_KERNEL_IMPL")
    check(impl in (None, "", "pallas"),
          f"REPRO_KERNEL_IMPL={impl!r}: the smoke runs the Pallas kernels")
    import jax

    from repro.kernels import ops
    dev = jax.devices()[0]
    check(dev.platform == "tpu", f"no TPU: JAX runs on {dev.platform}")
    check(ops._resolve("auto") == "pallas",
          f"kernels resolve to {ops._resolve('auto')!r}, not pallas")
    check(not ops._interpret(), "Pallas would run in interpret mode")
    return dev


# ---------------------------------------------------------------------------
# (b) kernel parity at full width
# ---------------------------------------------------------------------------

def _max_rel(got, want) -> tuple[float, float]:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = float(np.max(np.abs(got - want)))
    return err, err / max(float(np.max(np.abs(want))), 1e-30)


def kernel_parity(cfg, capacity: int, seed: int) -> None:
    """Each served kernel through Pallas and through its oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.lop import lop_features, pack_features
    from repro.core.ternary import pack_ternary
    from repro.kernels import ops
    from repro.serving.lop_select import k_keep_blocks
    from repro.serving.quantize import FFN_HIDDEN_ALIGN

    rng = np.random.default_rng(seed)
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    f = -(-cfg.d_ff // FFN_HIDDEN_ALIGN) * FFN_HIDDEN_ALIGN
    n_qkv = cfg.q_dim + 2 * cfg.kv_dim

    def codes(k, n):
        wt = rng.integers(-1, 2, (k, n)).astype(np.int8)
        return pack_ternary(jnp.asarray(wt))

    def both(fn, *args):
        got = jax.jit(lambda *a: fn(*a, impl="pallas"))(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda *a: fn(*a, impl="ref"))(*args)
        return jax.device_get(got), jax.device_get(want)

    failures = []

    def report(name, got, want, rtol, exact=False):
        err, rel = _max_rel(got, want)
        info(f"parity {name}: max|err| {err:.3e}, relative {rel:.3e} "
             f"(limit {'exact' if exact else rtol})")
        ok = (np.array_equal(np.asarray(got), np.asarray(want)) if exact
              else rel <= rtol)
        if not ok:
            failures.append(name)

    qkv = codes(d, n_qkv)
    gamma = jnp.asarray(rng.uniform(0.5, 1.5, (1, n_qkv)), jnp.float32)
    qkv_fn = lambda x, p, s, impl: ops.qlinear_fused(x, p, s, impl=impl)
    for m in (4, 128):                       # decode lanes, prefill chunk
        xi = rng.integers(-126, 127, (m, d)).astype(np.float32)
        xi[:, 0] = 127.0                     # absmax 127: exact barrier
        got, want = both(qkv_fn, jnp.asarray(xi), qkv,
                         jnp.ones((1, n_qkv), jnp.float32))
        report(f"qlinear qkv m={m} integer acc", got, want, 0, exact=True)
        x = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
        report(f"qlinear qkv m={m}", *both(qkv_fn, x, qkv, gamma),
               PROJ_RTOL)

    gu, down = codes(d, 2 * f), codes(f, d)
    gu_s = jnp.asarray(rng.uniform(0.5, 1.5, (1, 2 * f)), jnp.float32)
    down_s = jnp.full((1, 1), 0.8, jnp.float32)
    ffn_fn = lambda x, impl: ops.ffn_fused(x, gu, gu_s, down, down_s,
                                           gated=cfg.gated_ffn, act="silu",
                                           impl=impl)
    for m in (4, 128):
        x = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
        report(f"ffn m={m} (f {f})", *both(ffn_fn, x), PROJ_RTOL)

    b, m_cap = 4, capacity
    k_keep = k_keep_blocks(cfg, m_cap)
    ki = jnp.asarray(rng.integers(-127, 128, (b, hkv, m_cap, dh)), jnp.int8)
    vi = jnp.asarray(rng.integers(-127, 128, (b, hkv, m_cap, dh)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.005, 0.02, (b, hkv, m_cap)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.005, 0.02, (b, hkv, m_cap)), jnp.float32)
    feat = pack_features(lop_features(ki))
    qi = jnp.asarray(rng.integers(-127, 128, (b, h, dh)), jnp.int8)
    qs = jnp.asarray(rng.uniform(0.005, 0.02, (b, h, 1)), jnp.float32)
    new_len = jnp.asarray([m_cap - 1, 1000, 300, 0], jnp.int32)
    for use_lop in (True, False):
        dec_fn = lambda *a, impl, use_lop=use_lop: ops.decode_attention(
            *a, block=cfg.lop_block, k_keep=k_keep, use_lop=use_lop,
            impl=impl)
        got, want = both(dec_fn, qi, qs, ki, vi, ks, vs, feat, new_len)
        report(f"decode {'lop' if use_lop else 'dense'} (M {m_cap}, "
               f"k_keep {k_keep})", got, want, ATTN_RTOL)
        check(not np.asarray(got)[3].any(),
              "decode: a retired lane must emit exactly zero")

    c = cfg.lop_block
    pq = jnp.asarray(rng.integers(-127, 128, (1, h, c, dh)), jnp.int8)
    pqs = jnp.asarray(rng.uniform(0.005, 0.02, (1, h, c)), jnp.float32)
    pf_fn = lambda *a, impl: ops.prefill_attention(*a, q_offset=2 * c,
                                                   impl=impl)
    got, want = both(pf_fn, pq, pqs, ki[:1], vi[:1], ks[:1], vs[:1],
                     jnp.asarray([3 * c], jnp.int32))
    report(f"prefill (chunk {c}, M {m_cap})", got, want, ATTN_RTOL)
    check(not failures, f"kernel parity failed: {failures}")


# ---------------------------------------------------------------------------
# (c)-(e) the served path over HTTP
# ---------------------------------------------------------------------------

def _post(port: int, body: dict, timeout: float = 900.0):
    """One completion over a real socket → (status, tokens)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
    finally:
        conn.close()
    if resp.status != 200:
        return resp.status, raw.decode(errors="replace")
    if not body.get("stream"):
        return 200, json.loads(raw)["choices"][0]["tokens"]
    tokens = []
    for line in raw.decode().split("\n"):
        if line.startswith("data: ") and line[6:] != "[DONE]":
            event = json.loads(line[6:])
            check("choices" in event, f"stream error event: {event}")
            tokens.append(event["choices"][0]["token"])
    return 200, tokens


def serve_and_check(server_args, seed: int) -> dict:
    """Build, serve over loopback HTTP, compare with lockstep."""
    import jax
    import numpy as np

    from repro.launch.server import build_scheduler
    from repro.serving.frontend import serve_threaded
    from repro.serving.scheduler import lockstep_generate

    t0 = time.monotonic()
    sched = build_scheduler(server_args)
    jax.block_until_ready(sched.engine.qp)
    t_build = time.monotonic() - t0
    gib = lambda tree: sum(a.nbytes for a in jax.tree.leaves(tree)) / 2**30
    info(f"built {sched.cfg.name}: {sched.n_slots} slots, capacity "
         f"{sched.capacity} tokens, in {t_build:.1f}s; deployment weights "
         f"{gib(sched.engine.qp):.3f} GiB, KV pool {gib(sched.pool):.3f} GiB")

    rng = np.random.default_rng(seed + 1)
    prompts = [rng.integers(0, sched.cfg.vocab, (n,)).astype(int).tolist()
               for n in PROMPT_LENS]
    results: list = [None] * N_REQUESTS

    def client(i):
        try:
            results[i] = _post(srv.port, {"prompt": prompts[i],
                                          "max_tokens": MAX_TOKENS,
                                          "stream": i == STREAMED})
        except Exception as e:                        # noqa: BLE001
            results[i] = (-1, repr(e))

    srv = serve_threaded(sched, model_name=sched.cfg.name)
    try:
        t1 = time.monotonic()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(N_REQUESTS)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=1000.0)
        t_serve = time.monotonic() - t1
        check(not any(th.is_alive() for th in threads),
              "a client thread did not finish")
        pump_error = srv.frontend.pump.error
    finally:
        srv.close()
    info(f"served {N_REQUESTS} requests over HTTP in {t_serve:.1f}s "
         f"(compiles included)")

    check(pump_error is None, f"scheduler pump failed: {pump_error!r}")
    for i, (status, toks) in enumerate(results):
        check(status == 200, f"request {i}: HTTP {status}: {toks}")
        check(len(toks) == MAX_TOKENS,
              f"request {i}: {len(toks)} tokens, want {MAX_TOKENS}")
    store = sched.prefix_store
    counters = {"fault_events": sched.fault_events,
                "shed": sched.shed_count,
                "deadline": sched.deadline_count,
                "checksum_failures": store.checksum_failures if store
                else 0}
    info(f"counters {counters}")
    check(not any(counters.values()), f"nonzero counters {counters}")

    t2 = time.monotonic()
    for i, (_, toks) in enumerate(results):
        ref = lockstep_generate(sched.cfg, sched.engine.qp,
                                np.asarray(prompts[i], np.int32),
                                MAX_TOKENS, max_len=server_args.max_len,
                                engine=sched.engine)
        check(list(toks) == list(ref),
              f"request {i} (prompt {PROMPT_LENS[i]}): HTTP tokens "
              f"{toks} != lockstep {ref}")
    info(f"all {N_REQUESTS} requests token-identical to lockstep_generate "
         f"({time.monotonic() - t2:.1f}s)")

    # (e) the compiled decode step must contain the Pallas kernels
    eng = sched.engine
    n = sched.n_slots
    t3 = time.monotonic()
    compiled = eng._decode_greedy_fn.lower(
        eng.qp, sched.pool, np.zeros((n, 1), np.int32),
        np.zeros((n,), np.float32)).compile()
    t_compile = time.monotonic() - t3
    n_calls = compiled.as_text().count("tpu_custom_call")
    ma = compiled.memory_analysis()
    info(f"re-lowered and compiled the decode step ({n} lanes; serving "
         f"compiled it first) in {t_compile:.1f}s: "
         f"{n_calls} tpu_custom_call sites; arguments "
         f"{ma.argument_size_in_bytes / 2**30:.3f} GiB, temporaries "
         f"{ma.temp_size_in_bytes / 2**30:.3f} GiB")
    check(n_calls > 0, "the decode step holds no Pallas kernel")
    return {"build_s": t_build, "serve_s": t_serve,
            "compile_s": t_compile}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    t0 = time.monotonic()
    try:
        dev = require_tpu()
        from repro.launch import compile_cache
        from repro.launch.server import build_parser
        info(f"device {dev.device_kind}, compile cache "
             f"{compile_cache.enable()}")
        server_args = build_parser().parse_args(["--seed", str(args.seed)])
        from repro.launch.train import resolve_config
        from repro.serving.cache import round_up
        cfg = resolve_config(server_args.arch, server_args.reduced)
        t1 = time.monotonic()
        kernel_parity(cfg, round_up(server_args.max_len + 1, cfg.lop_block),
                      args.seed)
        info(f"kernel parity passed in {time.monotonic() - t1:.1f}s")
        times = serve_and_check(server_args, args.seed)
        stats = dev.memory_stats() or {}
        import jax
        info(f"smoke info (not metrics): device {dev.device_kind}, "
             f"weights built in {times['build_s']:.1f}s, HTTP phase "
             f"with warm-up compiles {times['serve_s']:.1f}s, decode-step "
             f"recompile {times['compile_s']:.1f}s, total "
             f"{time.monotonic() - t0:.1f}s, "
             f"HBM in use {stats.get('bytes_in_use', 0) / 2**30:.2f} GiB, "
             f"peak {stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB "
             f"of {stats.get('bytes_limit', 0) / 2**30:.2f} GiB")
        devices = jax.devices()
    except Exception:                                  # noqa: BLE001
        traceback.print_exc()
        print("[smoke] FAILED", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
