"""Trace reduction: busy and idle as a union of intervals, kernel time by
the name table (with a model module's own names merged in), program
spans of whole runs, idle gaps labelled by the host events in them."""

import pytest

from bench import harness, spans
from bench import trace as T

TABLE = T.load_kernel_table()
KERNELS, PROGRAMS = TABLE["kernels"], TABLE["programs"]
CONTAINERS = TABLE["containers"]


def ev(name, start, end):
    return T.Event(name, float(start), float(end - start), name)


def op(name, start, end):
    """An op event as the TPU trace names it: the HLO instruction."""
    label = f"%{name} = f32[8,128] custom-call(f32[8,128] %p)"
    return T.Event(label, float(start), float(end - start), label)


def small_trace():
    """One device: a decode step [0, 100) with overlapping ops, a gap,
    a prefill chunk [150, 200), a gap to the window's end (250)."""
    dev = T.Device("/device:TPU:0")
    dev.modules = [ev("jit_step_greedy(123)", 0, 100),
                   ev("jit_prefill_chunk(456)", 150, 200)]
    dev.ops = [op("while.7", 0, 100),                      # a container
               op("fused_qlinear.8", 0, 30),
               op("fused_decode_attention.8", 20, 50),     # overlaps
               op("fused_decode_attention.9", 50, 60),
               op("fusion.3", 60, 70),
               op("fused_ffn.4", 80, 100),
               op("fused_qlinear.2", 150, 190),
               op("fused_prefill_attention.1", 190, 200)]
    host = [ev("PjitFunction(step_greedy)", 60, 130),
            ev("checksum pages", 100, 150),
            ev("idle wait", 200, 250)]
    return T.Trace([dev], host, 250.0)


def test_union_and_busy():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    tr = small_trace()
    tr.devices[0].ops.pop(0)                       # without the while
    # [0,70) ∪ [80,100) ∪ [150,200) = 70 + 20 + 50
    assert T.busy_ns(tr.devices[0], tr.window_ns) == 140
    assert T.idle_gaps(tr.devices[0], tr.window_ns) == [
        (70, 80), (100, 150), (200, 250)]


def test_kernel_time_by_name_and_program():
    red = T.reduce(small_trace(), KERNELS, PROGRAMS, CONTAINERS)
    assert red.window_s == pytest.approx(250e-9)
    assert red.busy_s == pytest.approx(150e-9)     # the while spans 70-80
    assert red.program_spans == {"decode_step": [100e-9],
                                 "prefill_chunk": [50e-9]}
    assert red.kernel_s[("decode_attention", "decode_step")] == \
        pytest.approx(40e-9)
    assert red.kernel_calls[("decode_attention", "decode_step")] == 2
    assert red.kernel_s[("qlinear", "decode_step")] == pytest.approx(30e-9)
    assert red.kernel_s[("qlinear", "prefill_chunk")] == \
        pytest.approx(40e-9)
    assert red.kernel_s[("ffn", "decode_step")] == pytest.approx(20e-9)
    assert ("decode_attention", "prefill_chunk") not in red.kernel_s


def test_idle_gaps_are_labelled_by_host_events():
    tr = small_trace()
    tr.devices[0].ops.pop(0)
    red = T.reduce(tr, KERNELS, PROGRAMS, CONTAINERS)
    assert red.top_gaps == [["checksum pages", pytest.approx(50e-9)],
                            ["idle wait", pytest.approx(50e-9)],
                            ["PjitFunction(step_greedy)",
                             pytest.approx(10e-9)]]


def test_op_ranking_uses_short_names_and_skips_containers():
    red = T.reduce(small_trace(), KERNELS, PROGRAMS, CONTAINERS)
    assert red.top_ops[0] == ["fused_qlinear.2", pytest.approx(40e-9)]
    assert all(not name.startswith("while") for name, _ in red.top_ops)


def test_events_outside_the_window_are_clipped():
    tr = small_trace()
    tr.devices[0].ops.append(op("fused_qlinear.9", 240, 400))
    assert T.busy_ns(tr.devices[0], tr.window_ns) == 160
    red = T.reduce(tr, KERNELS, PROGRAMS, CONTAINERS)
    assert ("qlinear", None) not in red.kernel_calls


def cut_trace():
    """Window [0, 316): a prefill chunk cut by the profiler's start
    [0, 16), whole decode steps [20, 120) and [190, 290) around a whole
    chunk [130, 180), a chunk cut by its stop [300, 316)."""
    dev = T.Device("/device:TPU:0")
    dev.modules = [ev("jit_prefill_chunk(2)", 0, 16),
                   ev("jit_step_greedy(1)", 20, 120),
                   ev("jit_prefill_chunk(2)", 130, 180),
                   ev("jit_step_greedy(1)", 190, 290),
                   ev("jit_prefill_chunk(2)", 300, 316)]
    dev.ops = [op("fused_qlinear.2", m.start, m.end) for m in dev.modules]
    return T.Trace([dev], [], 316.0)


def test_program_spans_count_whole_runs_only():
    tr = cut_trace()
    assert [(m.start, m.end) for m in T.whole_runs(tr.devices[0], 316.0)] \
        == [(20, 120), (130, 180), (190, 290)]
    red = T.reduce(tr, KERNELS, PROGRAMS, CONTAINERS)
    assert red.whole_spans == {"decode_step": [100e-9, 100e-9],
                               "prefill_chunk": [50e-9]}
    assert red.program_spans["prefill_chunk"] == \
        pytest.approx([16e-9, 50e-9, 16e-9])
    ctx = harness.Context(reduction=red)
    assert harness.load_reader("decode_step_ms")(ctx) == \
        pytest.approx(100e-6)
    assert spans.prefill_chunk_ms(tr, PROGRAMS) == pytest.approx(50e-6)


def test_a_model_module_adds_names_to_the_table():
    extra = {"kernels": {"ffn": ["^%?grouped_ffn(\\.\\d+)? ="],
                         "mla": ["^%?mla_decode(\\.\\d+)? ="]},
             "programs": {"decode_step": ["^jit_step_mla\\b"]}}
    table = T.load_kernel_table(extra)
    assert table["kernels"]["ffn"] == KERNELS["ffn"] + extra["kernels"]["ffn"]
    assert list(table["kernels"]) == list(KERNELS) + ["mla"]
    assert table["programs"]["decode_step"][-1] == "^jit_step_mla\\b"
    assert table["containers"] == CONTAINERS
    assert T.load_kernel_table({}) == TABLE
    tr = small_trace()
    tr.devices[0].modules[0] = ev("jit_step_mla(7)", 0, 100)
    tr.devices[0].ops[3] = op("mla_decode.1", 50, 60)
    tr.devices[0].ops[5] = op("grouped_ffn.3", 80, 100)
    red = T.reduce(tr, table["kernels"], table["programs"],
                   table["containers"])
    assert red.kernel_s[("mla", "decode_step")] == pytest.approx(10e-9)
    assert red.kernel_s[("ffn", "decode_step")] == pytest.approx(20e-9)
