"""The program's own regions in a trace: reading them, naming idle gaps by
them, and the three readers built on them; on hand-made intervals, on a
CPU trace of the served path (``testdata/cpu_regions.xplane.pb`` and its
spans, made by ``testdata/record_regions.py``), and the readers that came
before them unchanged on ``testdata/cpu_trace.xplane.pb``."""

import json
import math
import os
from types import SimpleNamespace

import pytest

from chipbench import regions, roofline
from chipbench import trace_reduce as tr
from chipbench.metrics import (decode_step_roofline, device_idle_share,
                               first_delta_ms_p50, handoff_ms_p50,
                               segment_gap_us_p50, step_mfu)
from chipbench.regions import Region
from chipbench.run import RunData
from chipbench.trace_reduce import Interval, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "testdata")
OLD = os.path.join(DATA, "cpu_trace.xplane.pb")
NEW = os.path.join(DATA, "cpu_regions.xplane.pb")
U = 1000                       # ns per unit
W, L = ("/host:CPU", 0), ("/host:CPU", 1)     # worker and loop threads


def span(name, rid, t0, t1, **args):
    return SimpleNamespace(name=name, req_id=rid, t0=t0, t1=t1,
                           track="replica0", args=args or None,
                           dur=t1 - t0)


def config():
    with open(os.path.join(DATA, "tiny-llama.json")) as f:
        return json.load(f)


def run_data(trace=None, spans=(), responses=None):
    return RunData(config(), [], responses or {}, list(spans), None, trace,
                   tr.reduce(trace) if trace is not None else None,
                   roofline.peaks("TPU v5 lite"))


def synthetic():
    """Two segments of request 7 with a host gap between their programs,
    then a gap while the event loop dispatches request 8."""
    ops = [Interval("%fusion.1 = f()", 100 * U, 300 * U),
           Interval("%fusion.1 = f()", 500 * U, 700 * U),
           Interval("%fusion.2 = f()", 950 * U, 990 * U)]
    modules = [Interval("jit__segment_impl(1)", 100 * U, 300 * U),
               Interval("jit__segment_impl(1)", 500 * U, 700 * U),
               Interval("jit__lambda(2)", 950 * U, 990 * U)]
    marks = [Interval("chipbench.window", 0, 1000 * U)]
    regs = [Region("clairvoyant.decode_segment", 50 * U, 320 * U,
                   {"req_id": 7, "seg": 0}, W),
            Region("clairvoyant.decode_sync", 90 * U, 310 * U,
                   {"req_id": 7}, W),
            Region("clairvoyant.decode_emit", 310 * U, 318 * U,
                   {"req_id": 7}, W),
            Region("clairvoyant.decode_poll", 330 * U, 460 * U,
                   {"req_id": 7}, W),
            Region("clairvoyant.decode_segment", 470 * U, 720 * U,
                   {"req_id": 7, "seg": 1}, W),
            Region("clairvoyant.dispatch", 760 * U, 900 * U,
                   {"req_id": 8}, L),
            Region("clairvoyant.prefill", 940 * U, 995 * U,
                   {"req_id": 8}, W)]
    return (Trace({"/device:TPU:0": ops}, {"/device:TPU:0": modules}, marks),
            sorted(regs, key=lambda r: (r.start, -r.end)))


def test_gaps_are_named_by_the_worker_first():
    t, regs = synthetic()
    named = regions.idle_by_region(t, regs)
    # [0, 100]: midpoint 50 opens segment 0 (worker); [300, 500]: the poll;
    # [700, 950]: midpoint 825, nothing on the worker, the loop's dispatch;
    # [990, 1000]: 10 us, midpoint in the prefill
    assert named["clairvoyant.decode_segment"] == pytest.approx(100e-6)
    assert named["clairvoyant.decode_poll"] == pytest.approx(200e-6)
    assert named["clairvoyant.dispatch"] == pytest.approx(250e-6)
    assert named["clairvoyant.prefill"] == pytest.approx(10e-6)
    assert tr.OUTSIDE not in named
    regs = [r for r in regs if r.name != "clairvoyant.dispatch"]
    assert regions.idle_by_region(t, regs)[tr.OUTSIDE] == \
        pytest.approx(250e-6)


def test_segment_gap_is_the_device_idle_between_programs():
    t, regs = synthetic()
    assert regions.segment_gaps_ns(t, regs) == [200.0 * U]
    # a segment that started before the window is left out
    t.marks[0] = Interval("chipbench.window", 60 * U, 1000 * U)
    assert regions.segment_gaps_ns(t, regs) == []


def test_handoff_counts_back_to_back_dispatches_only():
    spans = [span("dispatch", 1, 0.0, 0.001),
             span("queue_wait", 1, 0.0, 0.0), span("prefill", 1, 0.002,
                                                   0.003),
             span("decode_segment", 1, 0.003, 0.010),
             span("decode_segment", 1, 0.010, 0.020),
             span("queue_wait", 2, 0.005, 0.021),      # waited: back to back
             span("prefill", 2, 0.0235, 0.025),
             span("decode_segment", 2, 0.025, 0.030),
             span("queue_wait", 3, 0.040, 0.040),      # arrived to an idle
             span("prefill", 3, 0.041, 0.042)]         # backend: not counted
    assert handoff_ms_p50.read(run_data(spans=spans)) == pytest.approx(3.5)
    # without the program's dispatch region the spans are not measured ones
    assert handoff_ms_p50.read(run_data(spans=spans[1:])) is None


def test_first_delta_reads_the_first_write_of_completed_requests():
    spans = [span("prefill", 1, 0.0, 0.004),
             span("sse_write", 1, 0.005, 0.0065, seg=0),
             span("sse_write", 1, 0.009, 0.010, seg=1),
             span("prefill", 2, 0.02, 0.03),
             span("sse_write", 2, 0.031, 0.032, seg=0)]
    ok = {1: SimpleNamespace(ok=True), 2: SimpleNamespace(ok=False)}
    assert first_delta_ms_p50.read(run_data(spans=spans, responses=ok)) \
        == pytest.approx(2.5)
    assert first_delta_ms_p50.read(run_data(spans=spans[:1],
                                            responses=ok)) is None


@pytest.fixture(scope="module")
def recorded():
    spans = []
    with open(os.path.join(DATA, "cpu_regions.spans.jsonl")) as f:
        for line in f:
            d = json.loads(line)
            if d["type"] == "span":
                spans.append(span(d["name"], d["req_id"], d["t0"], d["t1"],
                                  **d["args"]))
    ok = {s.req_id: SimpleNamespace(ok=True) for s in spans}
    return tr.read(NEW), regions.read(NEW), spans, ok


def test_recorded_regions_sit_on_their_threads(recorded):
    trace, regs, _, _ = recorded
    names = {r.name for r in regs}
    assert {"clairvoyant.prefill", "clairvoyant.decode_segment",
            "clairvoyant.decode_sync", "clairvoyant.decode_stop",
            "clairvoyant.dispatch",
            "clairvoyant.sse_write"} <= names
    worker = regions.worker_threads(regs)
    assert len(worker) == 1
    loop = {r.thread for r in regs if r.name == "clairvoyant.dispatch"}
    assert loop and not loop & worker
    segs = [r for r in regs if r.name == "clairvoyant.decode_segment"]
    assert all({"req_id", "seg", "plen", "first_step", "steps"}
               <= set(r.args) for r in segs)
    # the harness's reduction sees only its own spans
    assert {m.name for m in trace.marks} == {"chipbench.window"}


def test_recorded_gaps_are_named_by_program_regions(recorded):
    trace, regs, _, _ = recorded
    named = regions.idle_by_region(trace, regs)
    r = tr.reduce(trace)
    assert sum(named.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    by_program = sum(v for k, v in named.items()
                     if k.startswith(regions.PREFIX))
    assert by_program > 0
    assert {"clairvoyant.decode", "clairvoyant.decode_emit"} & set(named)


def test_new_readers_give_finite_values_on_the_recorded_run(recorded,
                                                            monkeypatch):
    trace, _, spans, ok = recorded
    monkeypatch.setattr(regions, "last_trace", lambda: NEW)
    rd = run_data(trace, spans, ok)
    values = [m.read(rd) for m in (segment_gap_us_p50, handoff_ms_p50,
                                   first_delta_ms_p50)]
    assert all(v is not None and math.isfinite(v) and v > 0
               for v in values), values
    monkeypatch.setattr(regions, "last_trace", lambda: None)
    assert segment_gap_us_p50.read(rd) is None


@pytest.mark.parametrize("reader, value", [
    (decode_step_roofline, 0.22196679506695272),
    (step_mfu, 7.892731057207823e-05),
    (device_idle_share, 92.91522778008473)])
def test_trace_readers_unchanged_on_the_old_trace(reader, value):
    assert reader.read(run_data(tr.read(OLD))) == pytest.approx(value,
                                                                rel=1e-12)


def test_old_trace_breakdown_unchanged():
    r = tr.reduce(tr.read(OLD))
    assert [n for n, _ in r["idle_gaps"]] == [
        tr.OUTSIDE, "chipbench.generate", tr.SHORT_GAPS]
    assert [v for _, v in r["idle_gaps"]] == pytest.approx(
        [0.075104745, 0.034313685, 2.9247e-05])
