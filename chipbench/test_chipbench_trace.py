"""The trace reduction, on hand-made intervals and on a small profiler
trace recorded on the CPU (``testdata/cpu_trace.xplane.pb``, made by
``testdata/record_trace.py``)."""

import os

import pytest

from chipbench import trace_reduce as tr
from chipbench.trace_reduce import Interval, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "testdata", "cpu_trace.xplane.pb")


U = 1000       # ns per unit: gaps of 100 units are 100 us


def synthetic():
    ops = [Interval("%while.1 = (...) while(...)", 100 * U, 400 * U),
           Interval("%fusion.1 = bf16[] fusion()", 110 * U, 200 * U),
           Interval("%fusion.2 = bf16[] fusion()", 200 * U, 390 * U),
           Interval("%fusion.1 = bf16[] fusion()", 600 * U, 650 * U),
           Interval("%copy.3 = bf16[] copy()", 640 * U, 700 * U)]
    modules = [Interval("jit__segment_impl(123)", 100 * U, 400 * U),
               Interval("jit__lambda(456)", 600 * U, 700 * U)]
    marks = [Interval("chipbench.window", 0, 1000 * U),
             Interval("chipbench.generate", 50 * U, 900 * U, {"plen": 3}),
             Interval("chipbench.segment", 60 * U, 80 * U,
                      {"plen": 3, "first_step": 0, "steps": 16}),
             Interval("chipbench.prefill", 500 * U, 520 * U, {"tokens": 3})]
    return Trace({"/device:TPU:0": ops}, {"/device:TPU:0": modules}, marks)


def test_busy_is_the_union_of_op_intervals():
    r = tr.reduce(synthetic())
    assert r["window_s"] == pytest.approx(1000e-6)
    # [100, 400] and [600, 700]: nested and overlapping ops count once
    assert r["busy_s"] == pytest.approx(400e-6)


def test_top_ops_by_self_time():
    r = tr.reduce(synthetic())
    ops = dict(r["device_ops"])
    assert ops["jit__segment_impl/fusion.2"] == pytest.approx(190e-6)
    assert ops["jit__segment_impl/fusion.1"] == pytest.approx(90e-6)
    # the while's own time is what its body's ops leave uncovered
    assert ops["jit__segment_impl/while.1"] == pytest.approx(20e-6)
    assert ops["jit__lambda/copy.3"] == pytest.approx(60e-6)
    assert r["device_ops"][0][0] == "jit__segment_impl/fusion.2"


def test_gaps_are_named_by_the_innermost_harness_span():
    r = tr.reduce(synthetic())
    gaps = dict(r["idle_gaps"])
    # [0, 100] (midpoint 50) and [700, 1000] lie in generate; the midpoint
    # of [400, 600] lies in the prefill span, the innermost there
    assert gaps["chipbench.generate"] == pytest.approx(400e-6)
    assert gaps["chipbench.prefill"] == pytest.approx(200e-6)
    t = synthetic()
    t.marks = [m for m in t.marks if m.name != "chipbench.generate"]
    gaps = dict(tr.reduce(t)["idle_gaps"])
    assert gaps[tr.OUTSIDE] == pytest.approx(400e-6)


def test_device_time_is_attributed_to_the_preceding_span():
    got = tr.device_time_by_mark(synthetic(), ("chipbench.prefill",
                                               "chipbench.segment"))
    assert [(m.name, ns) for m, ns in got] == [
        ("chipbench.segment", 300.0 * U), ("chipbench.prefill", 100.0 * U)]


def test_a_program_that_seems_to_start_before_its_span_is_its_spans():
    # as on the chip: each program is seen 1.5 units before the span whose
    # call launched it starts, and ends before the next span starts
    mods = [Interval("jit__lambda(1)", 1 * U, 3 * U),
            Interval("jit__segment_impl(2)", 8.5 * U, 36 * U),
            Interval("jit__segment_impl(2)", 38.5 * U, 45 * U)]
    marks = [Interval("chipbench.window", 0, 100 * U),
             Interval("chipbench.prefill", 2.5 * U, 4 * U, {"tokens": 3}),
             Interval("chipbench.segment", 10 * U, 10.5 * U,
                      {"plen": 3, "first_step": 0, "steps": 16}),
             Interval("chipbench.segment", 40 * U, 40.5 * U,
                      {"plen": 3, "first_step": 16, "steps": 4})]
    t = Trace({"/device:TPU:0": mods}, {"/device:TPU:0": mods}, marks)
    got = tr.device_time_by_mark(t, ("chipbench.prefill",
                                     "chipbench.segment"))
    assert [(m.args, ns) for m, ns in got] == [
        ({"tokens": 3}, 2.0 * U),
        ({"plen": 3, "first_step": 0, "steps": 16}, 27.5 * U),
        ({"plen": 3, "first_step": 16, "steps": 4}, 6.5 * U)]


def test_a_program_cut_by_the_window_is_left_out():
    t = synthetic()
    t.marks[0] = Interval("chipbench.window", 0, 680 * U)
    got = tr.device_time_by_mark(t, ("chipbench.prefill",
                                     "chipbench.segment"))
    assert [m.name for m, _ in got] == ["chipbench.segment"]


@pytest.fixture(scope="module")
def recorded():
    return tr.read(RECORDED)


def test_recorded_trace_reads_spans_and_ops(recorded):
    names = [m.name for m in recorded.marks]
    assert names.count("chipbench.window") == 1
    assert names.count("chipbench.segment") == 4
    assert names.count("chipbench.prefill") == 2
    seg = [m for m in recorded.marks if m.name == "chipbench.segment"]
    assert seg[1].args == {"plen": 12, "first_step": 16, "steps": 16}
    (dev,) = recorded.ops
    assert len(recorded.modules[dev]) == 6


def test_recorded_trace_reduction(recorded):
    r = tr.reduce(recorded)
    t0, t1 = tr.window(recorded)
    (dev,) = recorded.ops
    # busy by brute force: sweep over the sorted edges
    edges = sorted({t0, t1} | {max(t0, min(t1, x)) for i in recorded.ops[dev]
                               for x in (i.start, i.end)})
    busy = sum(b - a for a, b in zip(edges, edges[1:])
               if any(i.start <= a and i.end >= b
                      for i in recorded.ops[dev]))
    assert r["busy_s"] == pytest.approx(busy / 1e9)
    assert 0 < r["busy_s"] < r["window_s"]
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert r["device_ops"][0][0].startswith("jit__lambda/dot")
    labels = {n for n, _ in r["idle_gaps"]}
    assert "chipbench.generate" in labels and tr.OUTSIDE in labels
    got = tr.device_time_by_mark(recorded, ("chipbench.prefill",
                                            "chipbench.segment"))
    assert [m.name for m, _ in got] == ["chipbench.prefill",
                                        "chipbench.segment",
                                        "chipbench.segment"] * 2
