"""Launch the production HTTP serving front-end.

    PYTHONPATH=src python -m repro.launch.server --arch bitnet-3b \
        --reduced --port 8000

then stream a completion (the wire speaks token ids — the repo has no
tokenizer):

    curl -N http://127.0.0.1:8000/v1/completions \
        -d '{"prompt": [17, 42, 99], "max_tokens": 8, "stream": true}'

``GET /metrics`` serves the Prometheus-text registry the scheduler
writes into (DESIGN.md §Serving-metrics) — the same metric names
``repro.launch.serve`` reports, so a driver run and a live server are
diffable dashboards. ``--shape-log`` arms the log-and-sweep sidecar:
every distinct kernel dispatch shape the engine traces lands in a JSON
file that ``python -m repro.kernels.autotune --from-log`` sweeps later.
``--profile-port N`` starts JAX's profiler server on port N, so a
profiler client can capture a trace of the live server with the serve
cycle's spans in it (``serving/metrics.py`` ``SPANS``).
"""

from __future__ import annotations

import argparse
import asyncio

import jax

from repro.launch import compile_cache
from repro.launch.train import resolve_config
from repro.serving.api import PooledEngine
from repro.serving.frontend import HttpFrontend
from repro.serving.metrics import REGISTRY
from repro.serving.quantize import build_serving_params
from repro.serving.scheduler import Scheduler


def build_scheduler(args) -> Scheduler:
    cfg = resolve_config(args.arch, args.reduced)
    qp = build_serving_params(cfg, jax.random.PRNGKey(args.seed))
    engine = PooledEngine(cfg, qp, max_len=args.max_len,
                          use_lop=not args.no_lop,
                          chunk_tokens=args.chunk_tokens,
                          draft_layers=args.draft_layers,
                          draft_k=args.draft_k,
                          shape_log=args.shape_log)
    return Scheduler(
        cfg, qp, n_slots=args.slots, max_len=args.max_len,
        chunked=not args.no_chunked,
        prefix_cache=not args.no_prefix_cache,
        spec_decode=args.spec_decode, gamma=args.gamma,
        max_queue=args.max_queue, engine=engine, metrics=REGISTRY)


async def amain(args) -> None:
    sched = build_scheduler(args)
    frontend = HttpFrontend(sched, model_name=args.model_name or args.arch,
                            registry=REGISTRY)
    port = await frontend.start(args.host, args.port)
    print(f"serving {args.arch}{' (reduced)' if args.reduced else ''} "
          f"on http://{args.host}:{port}")
    print(f"  curl -N http://{args.host}:{port}/v1/completions "
          "-d '{\"prompt\": [17, 42, 99], \"max_tokens\": 8, "
          "\"stream\": true}'")
    print(f"  curl http://{args.host}:{port}/metrics")
    await frontend.serve_forever()


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="bitnet-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000,
                    help="0 picks a free port")
    ap.add_argument("--model-name", default=None,
                    help="name reported by /v1/models (default: --arch)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=2048,
                    help="per-slot KV capacity (prompt + generation)")
    ap.add_argument("--max-queue", type=int, default=64,
                    help="admission bound; beyond it requests get 429")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-lop", action="store_true")
    ap.add_argument("--no-chunked", action="store_true")
    ap.add_argument("--chunk-tokens", type=int, default=None)
    ap.add_argument("--no-prefix-cache", action="store_true")
    ap.add_argument("--spec-decode", action="store_true")
    ap.add_argument("--gamma", type=int, default=4)
    ap.add_argument("--draft-layers", type=int, default=None)
    ap.add_argument("--draft-k", type=int, default=None)
    ap.add_argument("--shape-log", default=None,
                    help="JSON sidecar recording kernel dispatch shapes "
                         "for `repro.kernels.autotune --from-log`")
    ap.add_argument("--profile-port", type=int, default=None,
                    help="start the JAX profiler server on this port, for "
                         "capturing traces of the live server")
    return ap


def main() -> None:
    args = build_parser().parse_args()
    compile_cache.enable()
    if args.profile_port is not None:
        jax.profiler.start_server(args.profile_port)
    try:
        asyncio.run(amain(args))
    except KeyboardInterrupt:
        print("shutting down")


if __name__ == "__main__":
    main()
