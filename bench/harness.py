"""One run of one cell: build, warm up, serve a timed window, check.

The cell, its configuration and its traffic mix are found by name:
``BENCHMARK.json`` names the cell's ``config`` and ``traffic``, read from
``bench/configs/<config>.json`` and ``bench/traffic/<traffic>.json``;
the configuration names its architecture's module
(``bench/models/<model>.py``, ``dense`` by default), which makes the
weights, runs the plain reference and counts the kernels' work; each
per-layer metric is read by ``bench/metrics/<name>.py``. A new cell is
data files and entries only, a new architecture a module besides.

A run:

1. refuses anything but the accelerator with the Pallas kernels;
2. makes the weights on the device from the seed (the model module);
3. builds ``PooledEngine`` and ``Scheduler`` from the configuration's
   ``server`` settings and serves them with ``serve_threaded`` on a
   loopback port;
4. warms up the cell's own shapes over HTTP (one request at the mix's
   longest prompt, then one per slot), counted in set-up;
5. lets the load generator (a child process that never imports JAX)
   drive ``/v1/completions`` for ``seconds``; with ``trace`` the
   profiler records a few seconds in the middle of the window;
6. reads the metrics, frees the server, and checks a seeded sample of
   the finished requests against the plain reference.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

from bench import models, stats, traffic
from bench import trace as T

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
TRACE_DIR = ROOT / ".bench_trace"
TRACE_MAX_S = 8.0          # profiler window, centred in the run's window
DRAIN_S = 60.0             # how long past the close a request may take
CHECK_TOKENS = 300         # served tokens the correctness sample reaches


class NoChip(RuntimeError):
    """The machine cannot run this cell as the benchmark defines it."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell's files
# ---------------------------------------------------------------------------

def load_spec(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def find_cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_config(name: str, directory: Path = BENCH_DIR / "configs") -> dict:
    return json.loads((Path(directory) / f"{name}.json").read_text())


def cell_metrics(spec: dict, cell: dict, section: str) -> list[dict]:
    """The entries of ``section`` this cell reports."""
    return [m for m in spec[section]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def load_reader(name: str, directory: Path = BENCH_DIR / "metrics"):
    path = Path(directory) / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def require_chip(chips: int):
    """The devices of a TPU run with the Pallas kernels, or NoChip."""
    impl = os.environ.get("REPRO_KERNEL_IMPL")
    if impl not in (None, "", "pallas"):
        raise NoChip(f"REPRO_KERNEL_IMPL={impl!r}: the benchmark runs the "
                     "Pallas kernels")
    import jax

    from repro.kernels import ops
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    if ops._resolve("auto") != "pallas" or ops._interpret():
        raise NoChip("the kernels would not run as compiled Pallas")
    return devs


def cache_every_program() -> str:
    """JAX's persistent cache by the program's rule
    (``repro.launch.compile_cache``: ``JAX_COMPILATION_CACHE_DIR``, else
    ``.jax_cache/`` in the checkout), holding every program: no minimum
    compile time or size. → the directory."""
    import jax

    from repro.launch import compile_cache
    directory = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return directory


class CompileCounter:
    """Programs compiled, and programs loaded from the persistent cache,
    with the seconds each took, per phase of the run."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "compiled",
              "/jax/compilation_cache/cache_retrieval_time_sec": "loaded"}

    def __init__(self):
        import jax.monitoring as mon
        self.phase = "setup"
        self.counts: dict = {}
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        kind = self.EVENTS.get(event)
        if kind is not None:
            n, s = self.counts.get((self.phase, kind), (0, 0.0))
            self.counts[(self.phase, kind)] = (n + 1, s + duration)

    def summary(self, phase: str) -> str:
        parts = []
        for kind in ("compiled", "loaded"):
            n, s = self.counts.get((phase, kind), (0, 0.0))
            parts.append(f"{n} programs {kind} in {s:.2f}s")
        return ", ".join(parts)


# ---------------------------------------------------------------------------
# the served system
# ---------------------------------------------------------------------------

def build_server(conf: dict, seed: int, registry, model):
    """→ (scheduler, served weights), the weights made from ``seed`` by
    the configuration's model module ``model``."""
    import jax

    from repro.serving.api import PooledEngine
    from repro.serving.scheduler import Scheduler

    cfg = model.model_config(conf)
    srv = conf["server"]
    qp = model.served_params(conf["arch"], seed)
    jax.block_until_ready(qp)
    engine = PooledEngine(cfg, qp, max_len=srv["max_len"],
                          use_lop=srv["use_lop"],
                          chunk_tokens=srv.get("chunk_tokens"))
    sched = Scheduler(cfg, qp, n_slots=srv["slots"], max_len=srv["max_len"],
                      chunked=srv.get("chunked", True),
                      prefix_cache=srv.get("prefix_cache", True),
                      max_queue=srv.get("max_queue"), engine=engine,
                      metrics=registry)
    return sched, qp


def warm_up(port: int, conf: dict, mix: dict, seed: int) -> None:
    """The cell's shapes, off the clock: one request at the mix's longest
    prompt runs every program the window uses (each prefill-chunk
    position and its prefix-page slices, the decode step over all slots,
    the samplers, interning and eviction of a lane)."""
    from bench import loadgen

    vocab = conf["arch"]["vocab"]
    n = traffic.max_prompt(mix)
    rec: dict = {"max_tokens": 2}
    loadgen.stream_one(port, traffic.prompt(seed, 1000, 0, n, vocab), 2, rec)
    if not stats.ok(rec):
        raise RuntimeError(f"warm-up request failed: {rec.get('status')} "
                           f"{rec.get('error')}")


class Served:
    """The served system of one seed: weights, scheduler and the loopback
    HTTP server, and the load generator that drives it; ``model`` is the
    configuration's model module."""

    def __init__(self, conf: dict, mix: dict, seed: int, model):
        from repro.serving.frontend import serve_threaded
        from repro.serving.metrics import MetricsRegistry

        self.conf, self.mix, self.seed = conf, mix, seed
        self.child = None
        self.srv = None
        try:
            self.registry = MetricsRegistry()
            self.sched, self.qp = build_server(conf, seed, self.registry,
                                               model)
            self.srv = serve_threaded(self.sched, model_name=conf["name"],
                                      registry=self.registry)
        except BaseException:
            self.close()
            raise

    def warm_up(self) -> None:
        """:func:`warm_up`, then wait until the scheduler has retired the
        warm-up lane: the stream ends with its last token, and the lane's
        eviction, a program the window runs too, follows on the
        scheduler's thread."""
        warm_up(self.srv.port, self.conf, self.mix, self.seed)
        deadline = time.monotonic() + DRAIN_S
        while self.sched.n_active:
            if time.monotonic() > deadline:
                raise RuntimeError("the warm-up lane was never retired")
            time.sleep(0.01)

    def window(self, seconds: float, during=None) -> dict:
        """Send the mix for ``seconds``; ``during(t_go)`` runs on this
        thread meanwhile. → the load generator's records, with the
        window's start."""
        job = {"port": self.srv.port, "seconds": seconds,
               "drain_s": DRAIN_S, "seed": self.seed,
               "vocab": self.conf["arch"]["vocab"], "mix": self.mix}
        self.child = subprocess.Popen(
            [sys.executable, str(BENCH_DIR / "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        self.child.stdin.write(json.dumps(job) + "\ngo\n")
        self.child.stdin.flush()
        t_go = time.monotonic()
        extra = during(t_go) if during is not None else None
        out, _ = self.child.communicate(timeout=seconds + DRAIN_S + 120)
        if self.child.returncode:
            raise RuntimeError(
                f"load generator exited {self.child.returncode}")
        run = json.loads(out)
        run.update(t_go=t_go, during=extra)
        if self.srv.frontend.pump.error is not None:
            raise RuntimeError(
                f"scheduler pump failed: {self.srv.frontend.pump.error!r}")
        return run

    def close(self) -> None:
        """Stop the server and the child, and drop the device state."""
        if self.srv is not None:
            self.srv.close()
        if self.child is not None and self.child.poll() is None:
            self.child.kill()
            self.child.wait()
        self.srv = self.sched = self.qp = None
        gc.collect()


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, root: Path = ROOT,
             check_chip: bool = True, configs_dir: Path | None = None,
             traffic_dir: Path | None = None,
             metrics_dir: Path | None = None,
             models_dir: Path | None = None) -> dict:
    """Everything of one run; → the result object of the last line."""
    spec = load_spec(root)
    cell = find_cell(spec, cell_name)
    conf = load_config(cell["config"], configs_dir or BENCH_DIR / "configs")
    mix = traffic.load(cell["traffic"], traffic_dir or traffic.DIR)
    model = models.load(conf, models_dir)

    import jax

    devs = require_chip(cell["chips"]) if check_chip else jax.devices()
    dev = devs[0]
    counter = CompileCounter()

    t0 = time.monotonic()
    served = Served(conf, mix, seed, model)
    try:
        t1 = time.monotonic()
        served.warm_up()
        log(f"set-up: process start to weights and server "
            f"{t1 - t_process:.2f}s (of which build {t1 - t0:.2f}s), "
            f"warm-up {time.monotonic() - t1:.2f}s; "
            f"{counter.summary('setup')}")
        counter.phase = "window"
        run = served.window(seconds, during=(
            (lambda t_go: trace_window(t_go, seconds)) if trace else None))
        counter.phase = "after"
        mem = dev.memory_stats() or {}
    finally:
        served.close()
    del served
    setup_s = run["t_start"] - t_process

    recs = stats.due_in_window(run)
    failed = [r for r in recs if not stats.ok(r)]
    lateness = stats.lateness_ms(run)
    log(f"load generator: {len(recs)} requests due in the window, "
        f"{len(failed)} failed, {run['unfinished']} unfinished at the "
        f"drain limit; lateness p95 "
        f"{stats.percentile(lateness, 95) if lateness else 0.0:.3f} ms; "
        f"inside the window: {counter.summary('window')}")

    result = {"correct": False, "attempted": len(recs),
              "failed": len(failed),
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devs),
                         "memory_peak_bytes": int(
                             mem.get("peak_bytes_in_use", 0))}}
    units = {m["name"]: m["unit"] for m in spec["end_to_end"]
             + spec["per_layer"]}
    e2e = stats.end_to_end(run)
    e2e["setup_s"] = setup_s
    log(f"end to end: {json.dumps(e2e)}")
    if trace:
        tr = run["during"]
        table = T.load_kernel_table(model.KERNELS)
        red = tr.reduce(table)
        result["device"]["busy_s"] = red.busy_s
        result["device"]["window_s"] = red.window_s
        result["breakdown"] = {"device_ops": red.top_ops,
                               "idle_gaps": red.top_gaps}
        ctx = Context(conf=conf, cell=cell, run=run, reduction=red,
                      trace_span=(tr.t0, tr.t1), device_kind=dev.device_kind,
                      model=model, table=table)
        metrics = {}
        for m in cell_metrics(spec, cell, "per_layer"):
            value = load_reader(m["name"], metrics_dir
                                or BENCH_DIR / "metrics")(ctx)
            if value is not None:
                metrics[m["name"]] = value
    else:
        metrics = {m["name"]: e2e[m["name"]]
                   for m in cell_metrics(spec, cell, "end_to_end")
                   if m["name"] in e2e}
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in metrics.items()}

    from bench.reference import check
    verdict = check.check(conf, mix, run, seed, sample_tokens=CHECK_TOKENS,
                          model=model)
    result["correct"] = bool(verdict["ok"] and not run["unfinished"]
                             and recs)
    result["compared"] = verdict["compared"]
    for line in verdict["lines"]:
        print(line, file=sys.stderr, flush=True)
    return result


class Context:
    """What a per-layer reader gets: the configuration, the cell, the
    load generator's records, the trace reduction, the host span of the
    trace, the device kind (for the peaks), the configuration's model
    module (for the counts) and the kernel name table."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


class trace_window:
    """Profile a few seconds in the middle of the window (from the main
    thread, which is otherwise idle while the window runs)."""

    def __init__(self, t_go: float, seconds: float):
        import jax

        length = min(TRACE_MAX_S, seconds / 2)
        start = t_go + (seconds - length) / 2
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        TRACE_DIR.mkdir(parents=True)
        time.sleep(max(0.0, start - time.monotonic()))
        self.t0 = time.monotonic()
        jax.profiler.start_trace(str(TRACE_DIR))
        time.sleep(max(0.0, start + length - time.monotonic()))
        self.t1 = time.monotonic()
        jax.profiler.stop_trace()

    def path(self) -> Path:
        found = sorted(TRACE_DIR.glob("**/*.xplane.pb"))
        if not found:
            raise RuntimeError(f"no trace written under {TRACE_DIR}")
        return found[-1]

    def reduce(self, table: dict):
        """The trace reduced by the kernel name ``table``."""
        tr = T.load(self.path(), (self.t1 - self.t0) * 1e9)
        return T.reduce(tr, table["kernels"], table["programs"],
                        table.get("containers", ()))
