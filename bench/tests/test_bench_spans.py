"""The readers of the program's own names on a synthetic trace: the LOP
screen's time per decode step, the prefill-chunk program's span, and
device idle inside the serve cycles split by the innermost span; each
None where the program writes none of these names."""

import functools

import pytest

from bench import harness, spans
from bench import trace as T

PROGRAMS = T.load_kernel_table()["programs"]


def ev(name, start, end):
    return T.Event(name, float(start), float(end - start), name)


def op(name, start, end, scope=""):
    """An op event as a TPU trace names it: the instruction's text, with
    the phase's mark among its frontend attributes (a launch's in its
    kernel metadata, a fusion's as an attribute of its own)."""
    if name.startswith("fused_decode_attention"):
        label = f"%{name} = f32[8,128] custom-call(f32[8,128] %p)"
        mark = f'kernel_metadata={{\n"scope":"{scope}"\n}}'
    else:
        label = f"%{name} = f32[8,128] fusion(f32[8,128] %p), kind=kLoop"
        mark = f'scope="{scope}"'
    if scope:
        label += f", frontend_attributes={{{mark}}}"
    return T.Event(label, float(start), float(end - start), label)


def synthetic(screen=True, chunk_program="jit_prefill_chunk", cycles=True):
    """Window [0, 1000). Device: decode steps [0, 300) and [450, 750), a
    prefill chunk [350, 400), a step and a chunk past the window's end.
    Busy: [10, 300), [350, 400), [460, 700), [910, 990)."""
    dev = T.Device("/device:TPU:0")
    dev.modules = [ev("jit_step_greedy(1)", 0, 300),
                   ev(f"{chunk_program}(2)", 350, 400),
                   ev("jit_step_greedy(1)", 450, 750),
                   ev("jit_step_greedy(1)", 900, 1100),
                   ev(f"{chunk_program}(2)", 950, 1050)]
    s = "lop_screen" if screen else ""
    dev.ops = [op("fused_decode_attention.8", 10, 200, s),
               op("fusion.3", 200, 210, s),                 # candidates
               op("fused_decode_attention.9", 210, 230, "lop_exact"),
               op("fused_qlinear.8", 230, 300),
               op("fused_qlinear.2", 350, 400),
               op("fused_decode_attention.8", 460, 640, s),
               op("while.7", 470, 480, s),                  # nested
               op("fusion.3", 640, 650, s),
               op("fused_decode_attention.9", 650, 700, "lop_exact"),
               op("fused_decode_attention.8", 910, 990, s)]
    host = [ev("_time_sleep", 0, 1000)]
    if cycles:
        host += [
            # cycle A [0, 440)
            ev("repro.cycle", 0, 440), ev("repro.admit", 0, 4),
            ev("repro.decode.dispatch", 4, 10),
            ev("repro.decode.readback", 10, 300),
            ev("repro.emit", 300, 320), ev("repro.finish", 320, 330),
            ev("repro.prefill_chunk", 340, 350),
            # cycle B [440, 800), a first token that finishes its request
            ev("repro.cycle", 440, 800),
            ev("repro.decode.dispatch", 445, 455),
            ev("repro.decode.readback", 455, 700),
            ev("repro.emit", 700, 720),
            ev("repro.intern_prefix", 720, 760),
            ev("repro.first_token", 760, 790),
            ev("repro.finish", 780, 790),
            # an idle cycle, and one past the window's end
            ev("repro.cycle", 800, 900), ev("repro.inbox_wait", 800, 890),
            ev("repro.cycle", 900, 1100),
            ev("repro.decode.dispatch", 905, 910)]
    return T.Trace([dev], host, 1000.0)


def test_lop_screen_time_per_decode_step():
    # steps in the window: (190 + 10) and (180 + 10, the nested op once)
    assert spans.lop_screen_ms(synthetic(), PROGRAMS) == \
        pytest.approx(195e-6)


def test_prefill_chunk_span():
    assert spans.prefill_chunk_ms(synthetic(), PROGRAMS) == \
        pytest.approx(50e-6)


def test_cycle_idle_split_by_innermost_span():
    got = spans.cycle_idle(synthetic())
    # cycle A idles [0,10) [300,350) [400,440): 100; cycle B [440,460)
    # [700,800): 120; the idle cycle and the one past the end are left out
    assert got.cycles == 2
    assert got.mean_ms == pytest.approx(110e-6)
    want = {"repro.cycle": 32.5, "repro.emit": 20, "repro.intern_prefix": 20,
            "repro.first_token": 10, "repro.finish": 10,
            "repro.decode.dispatch": 8, "repro.prefill_chunk": 5,
            "repro.admit": 2, "repro.decode.readback": 2.5}
    assert got.split_ms == pytest.approx({k: v * 1e-6
                                          for k, v in want.items()})
    assert sum(got.split_ms.values()) == pytest.approx(got.mean_ms)
    # [0,10) [300,350) [400,460) [700,910) [990,1000)
    assert got.window_idle_ms == pytest.approx(340e-6)
    assert spans.cycle_idle_ms(synthetic()) == pytest.approx(110e-6)


@pytest.mark.parametrize("reader,trace", [
    (functools.partial(spans.lop_screen_ms, programs=PROGRAMS),
     synthetic(screen=False)),
    (functools.partial(spans.prefill_chunk_ms, programs=PROGRAMS),
     synthetic(chunk_program="jit_run")),
    (spans.cycle_idle_ms, synthetic(cycles=False)),
    (spans.cycle_idle_ms, T.Trace([], synthetic().host, 1000.0)),
], ids=["no-scope", "old-program-name", "no-spans", "no-device"])
def test_absent_names_read_none(reader, trace):
    assert reader(trace) is None


class _Window:
    t0, t1 = 0.0, 1e-6

    def path(self):
        return "synthetic.xplane.pb"


@pytest.mark.parametrize("name,want", [
    ("lop_screen_ms", 195e-6), ("prefill_chunk_ms", 50e-6),
    ("cycle_idle_ms", 110e-6)])
def test_readers_read_the_run_trace(monkeypatch, name, want):
    asked = []

    def load(path, window_ns):
        asked.append((path, window_ns))
        return synthetic()

    monkeypatch.setattr(spans, "_load", load)
    ctx = harness.Context(run={"during": _Window()},
                          table={"programs": PROGRAMS})
    assert harness.load_reader(name)(ctx) == pytest.approx(want)
    assert asked == [("synthetic.xplane.pb", pytest.approx(1000.0))]
