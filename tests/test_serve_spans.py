"""The serve cycle's profiler spans (``serving/metrics.py`` ``SPANS``).

A reduced scheduler (2 slots, chunked prefill, prefix cache on) runs a
few requests under ``jax.profiler.trace``; the trace read back holds
every span that path exercises, each decode dispatch and readback
inside a cycle, and the request's ``rid`` on every request-level span.
The scheduler pump's idle wait is a span of its own, and ``span``
refuses a name outside ``SPANS``.
"""

import threading

import jax
import numpy as np
import pytest

from bench import trace as T
from repro.serving import metrics
from repro.serving.metrics import SPANS, span

REQUEST_SPANS = ("repro.prefill_chunk", "repro.intern_prefix",
                 "repro.first_token", "repro.finish")


def _trace_file(directory):
    found = sorted(directory.glob("**/*.xplane.pb"))
    assert found, f"no trace under {directory}"
    return found[-1]


def _span_stats(path) -> list:
    """[(name, {stat: value})] of every repro.* host event."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((e.name, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    from repro.models.transformer import init_params
    from repro.serving.api import GenerateRequest
    from repro.serving.quantize import quantize_params
    from repro.serving.scheduler import Scheduler

    from tests.test_models_smoke import _reduced

    cfg = _reduced("bitnet-3b")
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    qp = quantize_params(cfg, params)
    sched = Scheduler(cfg, qp, n_slots=2, max_len=127, chunked=True,
                      prefix_cache=True, metrics=metrics.MetricsRegistry())
    assert sched.prefix_store is not None
    rng = np.random.default_rng(5)
    # prompts past one prefix block (32), so finished prompts intern
    lens = {0: 40, 1: 70, 2: 36}
    for rid, n in lens.items():
        sched.submit(GenerateRequest(
            rid=rid, prompt=rng.integers(0, cfg.vocab, (n,)).astype(
                np.int32), max_new_tokens=3))
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        sched.run_to_completion()
    path = _trace_file(out)
    return T.load(path, float("inf")), _span_stats(path), set(lens)


def test_the_path_writes_every_span_it_exercises(traced):
    tr, _, _ = traced
    names = {e.name for e in tr.host if e.name.startswith("repro.")}
    assert names <= set(SPANS)
    # the pump's idle wait and the speculative launches are other paths
    assert names == set(SPANS) - {"repro.inbox_wait", "repro.spec_draft",
                                  "repro.spec_verify"}


def test_decode_dispatch_and_readback_nest_in_a_cycle(traced):
    tr, _, _ = traced
    cycles = [e for e in tr.host if e.name == "repro.cycle"]
    for name in ("repro.decode.dispatch", "repro.decode.readback"):
        inner = [e for e in tr.host if e.name == name]
        assert inner, name
        for e, c in zip(inner, T.containing(cycles, inner)):
            assert c is not None and e.end <= c.end, name


def test_request_spans_carry_the_rid(traced):
    _, stats, rids = traced
    for name in REQUEST_SPANS:
        seen = {st.get("rid") for n, st in stats if n == name}
        assert seen and seen <= rids, (name, seen)
    # every request starts its lane and is finished under its own rid
    for name in ("repro.first_token", "repro.finish"):
        assert {st["rid"] for n, st in stats if n == name} == rids, name
    assert all("chunk" in st and "tokens" in st
               for n, st in stats if n == "repro.prefill_chunk")
    admitted = [st["admitted"] for n, st in stats if n == "repro.admit"]
    assert sum(admitted) == len(rids)


class _IdleScheduler:
    """What the pump asks of a scheduler, with no work ever."""
    results: list = []

    def has_work(self):
        return False

    def admit(self):
        return 0


def test_an_idle_pump_waits_inside_a_cycle(tmp_path):
    from repro.serving.frontend.server import SchedulerPump

    pump = SchedulerPump(_IdleScheduler(), idle_poll_s=0.005)
    with jax.profiler.trace(str(tmp_path)):
        pump.start()
        threading.Event().wait(0.1)
        pump.stop()
        pump.join(timeout=10)
    assert not pump.is_alive() and pump.error is None
    tr = T.load(_trace_file(tmp_path), float("inf"))
    cycles = [e for e in tr.host if e.name == "repro.cycle"]
    waits = [e for e in tr.host if e.name == "repro.inbox_wait"]
    assert waits and all(c is not None
                         for c in T.containing(cycles, waits))


def test_span_refuses_a_name_outside_spans():
    with pytest.raises(ValueError, match="metrics.SPANS"):
        span("repro.decode")
    with span("repro.cycle"):
        pass
