"""Host regions: the serve path timing its own work on the recorder's
clock and under ``clairvoyant.*`` profiler annotations.

A toy ``RealEngine`` behind ``InProcessBackend`` and a loopback
``Sidecar``: every ok request's measured ``decode_segment`` spans match
the engine's segment count and nest in its ``decode``, ``queue_wait``
ends at dispatch, and ``validate()`` is clean.  Without a recorder no
annotation is built and nothing is recorded.  The virtual-time
real-engine drain keeps a clean span tree with the engine's measured
spans.
"""

import asyncio

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.serving.backends import HTTPBackend, InProcessBackend
from repro.serving.engine import RealEngine
from repro.serving.http_sidecar import Sidecar
from repro.serving.observability import (NO_REGION, FlightRecorder,
                                         Observability, anchored_clock)
from repro.serving.openai_api import CompletionRequest
from repro.serving.server import ClairvoyantServer

HOST_REGIONS = {"dispatch", "finish", "sse_write", "decode_poll",
                "decode_dispatch", "decode_sync", "decode_emit",
                "decode_stop"}
TIMELINE = {"request", "queue_wait", "prefill", "decode", "decode_segment"}


@pytest.fixture(scope="module")
def cfg():
    return get_config("smollm-360m").reduced()


class CountingAnnotation(jax.profiler.TraceAnnotation):
    made = []

    def __init__(self, name, **kw):
        CountingAnnotation.made.append(name)
        super().__init__(name, **kw)


def serve_live(cfg, tracing, n=4):
    """Streamed requests through the sidecar; returns (server, engine
    segment counts by request id)."""
    eng = RealEngine(cfg, max_len=64, segment_len=4)
    segments = {}
    generate = eng.generate

    def counted(*a, req_id=None, **kw):
        out = generate(*a, req_id=req_id, **kw)
        segments[req_id] = out["segments"]
        return out

    eng.generate = counted

    async def run():
        srv = ClairvoyantServer(
            policy="fcfs", predictor=None, engines=[InProcessBackend(eng)],
            seed=0, deadline_mode="sojourn",
            observability=Observability.default(tracing=tracing))
        sc = Sidecar(srv, port=0, max_new_tokens=24)
        await sc.start()
        client = HTTPBackend("127.0.0.1", sc.port)
        outs = await asyncio.gather(*[
            client.generate(f"prompt number {i} " * (1 + i),
                            max_new_tokens=6 + 5 * i,
                            on_segment=lambda d: None)
            for i in range(n)])
        assert all(not o["cancelled"] for o in outs)
        await sc.shutdown(drain_s=5.0)
        return srv

    return asyncio.run(run()), segments


def test_live_inprocess_trace_is_measured_and_valid(cfg):
    srv, segments = serve_live(cfg, tracing=True)
    rec = srv.obs.recorder
    ok = [r for r in srv.responses if r.ok]
    assert len(ok) == 4
    assert rec.validate(srv._terminal, [r.request_id for r in ok]) == []
    names = {s.name for s in rec.spans()}
    assert HOST_REGIONS <= names
    assert set(rec.schema()) == TIMELINE         # host regions left out
    plens = []
    for resp in ok:
        rid = resp.request_id
        spans = rec.spans_for(rid)
        by = {}
        for s in spans:
            by.setdefault(s.name, []).append(s)
        segs = by["decode_segment"]
        assert len(segs) == segments[rid] >= 1
        assert [s.args["seg"] for s in segs] == list(range(len(segs)))
        (dec,) = by["decode"]
        (pre,) = by["prefill"]
        (qw,) = by["queue_wait"]
        (dsp,) = by["dispatch"]
        for s in spans:
            if s.name.startswith("decode_"):
                assert dec.t0 <= s.t0 <= s.t1 <= dec.t1, s
                assert s.track == dec.track == "replica0"
        # queue_wait ends at dispatch (req.start), as the response says
        assert qw.t1 - qw.t0 == resp.queue_wait_s
        assert dsp.t0 <= qw.t1 <= dsp.t1 <= pre.t0 <= pre.t1 <= dec.t0
        plens.append(pre.args["tokens"])
        # streamed: one sse_write per delta, the first one seg 0
        writes = sorted(by["sse_write"], key=lambda s: s.t0)
        assert [s.args["seg"] for s in writes] == list(range(len(writes)))
        assert len(writes) == 1 + segments[rid]
        assert writes[0].t1 > pre.t1
    assert sorted(plens) == [3, 6, 9, 12]        # one id per prompt word


@pytest.mark.parametrize("tracing", [False, True])
def test_annotations_only_with_a_recorder(cfg, monkeypatch, tracing):
    CountingAnnotation.made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", CountingAnnotation)
    srv, _ = serve_live(cfg, tracing=tracing, n=2)
    rec = srv.obs.recorder
    if not tracing:
        assert rec is None
        assert CountingAnnotation.made == []
        return
    regions = [s for s in rec.spans() if s.name in HOST_REGIONS
               or s.name in ("prefill", "decode", "decode_segment")]
    assert sorted(CountingAnnotation.made) == sorted(
        "clairvoyant." + s.name for s in regions)


def test_region_records_on_the_recorders_clock():
    rec = FlightRecorder()
    ticks = iter([10.0, 10.5, 11.0, 12.0])
    rec.clock = lambda: next(ticks)
    with rec.region("decode", 3, "replica0"):
        with rec.region("decode_sync", 3, "replica0", seg=1):
            pass
    (sync, dec) = rec.spans()
    assert (sync.name, sync.t0, sync.t1, sync.args) == \
        ("decode_sync", 10.5, 11.0, {"seg": 1})
    assert (dec.name, dec.t0, dec.t1, dec.args) == ("decode", 10.0, 12.0,
                                                    None)
    assert rec.schema() == ["decode"]
    with NO_REGION:
        pass


def test_region_takes_a_clock_of_its_own():
    rec = FlightRecorder()
    rec.clock = lambda: 1e9                      # not read
    clk = anchored_clock(7.0)
    with rec.region("feature_extract", 1, "req1", clock=clk, batch=2):
        pass
    with rec.region("predict", 1, "req1", clock=clk, batch=2):
        pass
    fx, pr = rec.spans()
    assert fx.t0 == 7.0 and fx.args == {"batch": 2}
    assert 7.0 <= fx.t1 <= pr.t0 <= pr.t1 < 8.0


def test_host_regions_may_outlive_the_root_but_must_nest():
    rec = FlightRecorder()
    rec.span("decode", 1, 0.0, 1.0, track="replica0")
    rec.request_span(1, 0.0, 1.0)
    rec.span("finish", 1, 1.0, 1.2, track="replica0")   # after the root
    assert rec.validate([1]) == []
    rec.span("dispatch", 2, 1.1, 1.3, track="replica0")  # overlaps finish
    assert any("overlaps" in p for p in rec.validate([1]))


def test_traced_real_drain_records_measured_spans(cfg):
    eng = RealEngine(cfg, max_len=64, segment_len=4)
    srv = ClairvoyantServer(policy="sjf", predictor=None, engines=[eng],
                            seed=0, observability=Observability.default())
    rng = np.random.default_rng(0)
    for i in range(5):
        srv.submit(CompletionRequest(prompt=f"real drain {i}"),
                   arrival=float(i) * 0.001,
                   true_output_tokens=int(rng.integers(3, 14)),
                   klass="short")
    rec = srv.obs.recorder
    clock = rec.clock
    srv.drain(max_new_tokens=14)
    assert rec.clock is clock                    # restored after dispatch
    ok = [r for r in srv.responses if r.ok]
    assert len(ok) == 5
    assert rec.validate(srv._terminal, [r.request_id for r in ok]) == []
    for resp in ok:
        spans = rec.spans_for(resp.request_id)
        by = {s.name: s for s in spans}
        qw, pre, dec, root = (by[k] for k in ("queue_wait", "prefill",
                                              "decode", "request"))
        assert qw.t1 - qw.t0 == pytest.approx(resp.queue_wait_s)
        assert {"decode_segment", "decode_sync",
                "decode_stop"} <= set(by)
        # the engine's spans start at the dispatch on the drain's clock
        # and end within its service: the root is the sojourn
        assert pre.t0 == qw.t1 <= pre.t1 <= dec.t0
        assert dec.t1 <= qw.t1 + resp.service_s
        assert (root.t0, root.t1) == (qw.t0, qw.t1 + resp.service_s)


def test_http_backend_stamps_spans_by_chunk_arrival():
    from repro.serving.backends import SimTextBackend

    async def run():
        upstream = ClairvoyantServer(
            policy="fcfs", predictor=None, deadline_mode="sojourn",
            engines=[SimTextBackend(time_scale=0.01, segment_tokens=8)])
        sc = Sidecar(upstream, port=0, max_new_tokens=32)
        await sc.start()
        be = HTTPBackend("127.0.0.1", sc.port)
        be.recorder = rec = FlightRecorder()
        deltas = []
        out = await be.generate("hi there", max_new_tokens=17,
                                on_segment=deltas.append, req_id=5)
        await sc.shutdown(drain_s=2.0)
        return rec, deltas, out

    rec, deltas, out = asyncio.run(run())
    assert out["tokens"] == 17 and len(deltas) == 3
    by = {}
    for s in rec.spans():
        assert s.req_id == 5 and s.track == "replica0"
        by.setdefault(s.name, []).append(s)
    (pre,), (dec,) = by["prefill"], by["decode"]
    segs = by["decode_segment"]
    assert [s.args["seg"] for s in segs] == [0, 1]
    assert pre.t0 < pre.t1 == dec.t0 == segs[0].t0
    assert segs[0].t1 == segs[1].t0 and segs[1].t1 <= dec.t1
    assert rec.validate([]) == []
