"""Observability suite (PR 10): flight recorder, /metrics, ranking monitor.

Covers trace lifecycle invariants (every admitted request yields exactly
one complete span tree; spans on exclusive tracks nest and never
overlap; Perfetto JSON round-trips with monotone ``ts``), Prometheus
exposition validity, the online ranking-fidelity monitor (recovery of a
known pairwise accuracy, inversion-drift alert within one window), the
DES-vs-live span-schema parity, and the sidecar's /metrics, /healthz
engine stats, and /readyz ranking + breaker detail.
"""

import asyncio
import json
import math

import numpy as np
import pytest

from repro.core.scheduler import Request
from repro.core.simulation import _spread_for_accuracy, simulate
from repro.serving.faults import CircuitBreaker, FaultPlan, RetryPolicy
from repro.serving.observability import (FlightRecorder, Histogram,
                                         MetricsRegistry, Observability,
                                         RankingMonitor, parse_prometheus,
                                         record_service_spans)
from repro.serving.openai_api import CompletionRequest
from repro.serving.server import ClairvoyantServer
from repro.serving.service_time import ServiceTimeModel


# ------------------------------------------------------------- recorder units
def test_recorder_ring_drops_and_counts():
    rec = FlightRecorder(capacity=4)
    for i in range(7):
        rec.span("decode", i, float(i), float(i) + 0.5)
    assert len(rec) == 4 and rec.dropped == 3
    assert [s.req_id for s in rec.spans()] == [3, 4, 5, 6]
    rec.clear()
    assert len(rec) == 0 and rec.dropped == 0


def test_request_span_stretches_over_straggler_children():
    rec = FlightRecorder()
    rec.span("decode", 1, 0.0, 5.0)           # child outlives the sojourn
    rec.request_span(1, 0.0, 3.0)
    root = rec.span_tree(1)["root"]
    assert root is not None and root.t1 == 5.0
    assert rec.validate([1]) == []


def test_validate_flags_missing_root_and_out_of_bounds():
    rec = FlightRecorder()
    rec.span("decode", 1, 0.0, 1.0)
    probs = rec.validate([1])
    assert any("root" in p for p in probs)     # no request span at all
    rec2 = FlightRecorder()
    rec2.span("request", 2, 0.0, 1.0, track="req2")
    rec2.span("decode", 2, 0.5, 2.0)           # ends after the root
    assert any("outside root" in p for p in rec2.validate([2]))


def test_validate_flags_partial_overlap_on_exclusive_track():
    rec = FlightRecorder()
    rec.span("decode", 1, 0.0, 2.0, track="replica0")
    rec.span("decode", 2, 1.0, 3.0, track="replica0")   # partial overlap
    assert any("overlaps" in p for p in rec.validate([]))
    # nesting and disjointness are both fine
    rec2 = FlightRecorder()
    rec2.span("decode", 1, 0.0, 2.0, track="replica0")
    rec2.span("decode_segment", 1, 0.5, 1.5, track="replica0")
    rec2.span("decode", 2, 2.0, 3.0, track="replica0")
    assert rec2.validate([]) == []


def test_async_spans_exempt_from_track_overlap():
    rec = FlightRecorder()
    rec.span("queue_wait", 1, 0.0, 5.0, track="req1")
    rec.span("queue_wait", 2, 1.0, 6.0, track="req1")   # same track, async
    assert rec.validate([]) == []


def test_record_service_spans_segments_cap():
    rec = FlightRecorder()
    record_service_spans(rec, 7, start=1.0, finish=9.0, arrival=0.0,
                         ttft=0.5, out_tokens=1000, segment_tokens=8,
                         max_segments=4)
    segs = [s for s in rec.spans() if s.name == "decode_segment"]
    assert len(segs) == 4                      # capped, not 125
    assert segs[0].t0 == pytest.approx(1.5)
    assert segs[-1].t1 == pytest.approx(9.0)
    # segments tile the decode span exactly
    for a, b in zip(segs, segs[1:]):
        assert a.t1 == pytest.approx(b.t0)


def test_perfetto_round_trips_with_monotone_ts():
    rec = FlightRecorder()
    for i in range(6):
        record_service_spans(rec, i, start=i * 1.0, finish=i * 1.0 + 0.9,
                             arrival=i * 0.5, ttft=0.1, out_tokens=32)
        rec.request_span(i, i * 0.5, i * 1.0 + 0.9)
    rec.instant("route", 0, 0.25, track="replica0")
    doc = json.loads(json.dumps(rec.to_perfetto()))
    evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)
    assert {e["ph"] for e in evs} >= {"X", "b", "e", "i"}
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "M"}
    assert names == {"process_name", "thread_name"}
    assert doc["otherData"]["dropped_spans"] == 0
    # jsonl export parses line by line
    for line in rec.jsonl_lines():
        assert json.loads(line)["type"] in ("span", "instant")


# ------------------------------------------------------------------- metrics
def test_metrics_render_is_valid_exposition():
    reg = MetricsRegistry()
    c = reg.counter("clairvoyant_test_total", "Things counted")
    g = reg.gauge("clairvoyant_test_depth", "A gauge")
    h = reg.histogram("clairvoyant_test_seconds", "A histogram",
                      buckets=(0.1, 1.0, 10.0))
    c.inc(3, status="ok", klass="short")
    c.inc(2, status="shed", klass="")
    g.set(7.5, replica="0")
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    fams = parse_prometheus(reg.render())
    assert fams["clairvoyant_test_total"][0][2] in (2.0, 3.0)
    hist = {n: v for n, lab, v in fams["clairvoyant_test_seconds"]}
    assert hist["clairvoyant_test_seconds_count"] == 4
    assert hist["clairvoyant_test_seconds_sum"] == pytest.approx(55.55)
    buckets = [(lab["le"], v) for n, lab, v in
               fams["clairvoyant_test_seconds"]
               if n.endswith("_bucket")]
    assert buckets == [("0.1", 1.0), ("1", 2.0), ("10", 3.0),
                       ("+Inf", 4.0)]


def test_histogram_fold_is_incremental():
    h = Histogram("x_seconds", "x", buckets=(1.0,))
    h.observe(0.5)
    assert h.count() == 1
    h.observe(2.0)
    h.observe(0.1)
    assert h.count() == 3                      # re-fold picks up new values


def test_parse_prometheus_rejects_malformed():
    with pytest.raises(ValueError):
        parse_prometheus("foo_total 1")        # no TYPE declaration
    with pytest.raises(ValueError):
        parse_prometheus("# TYPE x counter\nx{bad-label=\"1\"} 1")
    with pytest.raises(ValueError):
        parse_prometheus("# TYPE x counter\nx one_point_five")
    ok = parse_prometheus("# TYPE x counter\nx{a=\"b\"} 1.5\n")
    assert ok["x"] == [("x", {"a": "b"}, 1.5)]


# ------------------------------------------------------------ ranking monitor
def _feed_two_class(mon, rng, n, accuracy, invert=False,
                    s_short=1.0, s_long=8.0):
    """Noisy P(Long) keys at a target cross-class pairwise accuracy;
    within-class services are identical so those pairs are ties
    (excluded), leaving concordance == cross-class accuracy."""
    spread = _spread_for_accuracy(accuracy)
    for i in range(n):
        long = bool(i % 2)
        base = 0.75 if long else 0.25
        key = float(np.clip(rng.normal(base, spread), 0.0, 1.0))
        if invert:
            key = 1.0 - key
        mon.record(key, s_long if long else s_short,
                   p_long=key, is_long=long)


def test_ranking_monitor_recovers_known_accuracy():
    mon = RankingMonitor(window=512)
    _feed_two_class(mon, np.random.default_rng(7), 512, accuracy=0.87)
    snap = mon.snapshot()
    assert abs(snap["concordance"] - 0.87) <= 0.05
    assert not snap["alert"]
    assert snap["long_calibration_drift"] is not None
    assert snap["long_calibration_drift"] < 0.15


def test_ranking_monitor_alerts_on_inversion_within_one_window():
    mon = RankingMonitor(window=256, alert_threshold=0.6)
    rng = np.random.default_rng(3)
    _feed_two_class(mon, rng, 256, accuracy=0.9)
    assert not mon.snapshot()["alert"]
    # drift injection: the predictor inverts; within ONE window the
    # concordance collapses and the alert trips
    _feed_two_class(mon, rng, 256, accuracy=0.9, invert=True)
    snap = mon.snapshot()
    assert snap["alert"] and snap["concordance"] < 0.3


def test_ranking_monitor_ties_and_empty():
    mon = RankingMonitor(window=16)
    assert math.isnan(mon.concordance())
    for _ in range(4):
        mon.record(0.5, 2.0)                   # all ties -> still NaN
    assert math.isnan(mon.concordance())
    assert mon.snapshot()["concordance"] is None


def test_snapshot_cached_refreshes_on_dirty_threshold():
    mon = RankingMonitor(window=64)            # refresh every 8 records
    rng = np.random.default_rng(0)
    _feed_two_class(mon, rng, 16, accuracy=1.0)
    first = mon.snapshot_cached()
    mon.record(0.9, 9.0)
    assert mon.snapshot_cached() is first      # < window//8 new samples
    _feed_two_class(mon, rng, 8, accuracy=1.0)
    assert mon.snapshot_cached() is not first


# --------------------------------------------- traced drains (sim, chaos)
def _traced_chaos_server(seed, n_replicas=1, **kw):
    plan = FaultPlan.random(
        seed=seed, horizon=150.0, crash_mtbf=25.0, crash_mttr=3.0,
        transient_rate=1 / 20.0, stall_mtbf=40.0, stall_s=8.0,
        n_replicas=n_replicas)
    return ClairvoyantServer(policy="sjf", predictor=None, fault_plan=plan,
                             n_replicas=n_replicas, seed=seed,
                             retry=RetryPolicy(seed=seed),
                             observability=Observability.default(), **kw)


def test_chaos_sim_drain_span_trees_complete():
    """Every admitted request yields exactly one complete span tree,
    even under injected crashes/transients/cancels (the trace mirror of
    the no-lost-requests invariant)."""
    for trial in range(4):
        rng = np.random.default_rng(100 + trial)
        server = _traced_chaos_server(seed=trial, n_replicas=1 + trial % 2,
                                      deadline_s=None if trial % 2 else 40.0)
        n = 40
        ids = []
        for i in range(n):
            req = CompletionRequest(prompt=f"chaos {trial}:{i}")
            server.submit(req, arrival=float(rng.uniform(0, 100)),
                          true_output_tokens=int(rng.integers(20, 600)),
                          klass="short" if rng.random() < 0.6 else "long")
            ids.append(req.request_id)
        server.cancel(ids[1])
        server.drain()
        assert len(server.responses) == n
        rec = server.obs.recorder
        ok_ids = [r.request_id for r in server.responses if r.ok]
        problems = rec.validate(server._terminal, ok_ids)
        assert problems == [], f"trial {trial}: {problems[:5]}"
        # exactly one root per terminal
        for rid in ids:
            assert len(rec.span_tree(rid)["roots"]) == 1


def test_traced_preemptive_drain_validates():
    server = ClairvoyantServer(policy="srpt", predictor=None, seed=0,
                               observability=Observability.default())
    rng = np.random.default_rng(2)
    for i in range(30):
        server.submit(CompletionRequest(prompt=f"p{i}"),
                      arrival=float(rng.uniform(0, 20)),
                      true_output_tokens=int(rng.integers(20, 900)),
                      klass="short" if i % 3 else "long")
    server.drain()
    rec = server.obs.recorder
    ok_ids = [r.request_id for r in server.responses if r.ok]
    assert rec.validate(server._terminal, ok_ids) == []


def test_untraced_server_has_no_observability_cost_points():
    server = ClairvoyantServer(policy="sjf", predictor=None, seed=0)
    assert server.obs is None
    assert server.router.recorder is None
    server.submit(CompletionRequest(prompt="x"), true_output_tokens=10,
                  klass="short")
    server.drain()
    assert len(server.responses) == 1


def test_predictor_stage_spans_and_latency(small_predictor):
    obs = Observability.default()
    server = ClairvoyantServer(policy="sjf", predictor=small_predictor,
                               seed=0, observability=obs)
    reqs = [CompletionRequest(prompt=f"tell me about topic {i} " * (2 + i))
            for i in range(8)]
    server.submit_many(reqs, true_output_tokens=[30 + 10 * i
                                                for i in range(8)])
    server.drain()
    rec = obs.recorder
    names = rec.schema()
    assert "feature_extract" in names and "predict" in names
    h = obs.metrics._metrics["clairvoyant_predictor_latency_seconds"]
    assert h.count() == 8                      # per-request latencies


def test_traced_sim_drain_times_admission_at_arrival(small_predictor):
    """A virtual-time drain's admission regions start at each request's
    arrival on the drain's clock, so every root span is the request's
    sojourn and the recorder's own clock is left as it was."""
    obs = Observability.default()
    rec = obs.recorder
    clock = rec.clock
    server = ClairvoyantServer(policy="sjf", predictor=small_predictor,
                               seed=0, observability=obs)
    arrivals = [0.25 * i for i in range(6)]
    for i, a in enumerate(arrivals):
        server.submit(CompletionRequest(prompt=f"explain topic {i} " * 3),
                      arrival=a, true_output_tokens=40 + 15 * i)
    server.submit_many([CompletionRequest(prompt=f"burst {i}")
                        for i in range(3)], arrivals=[2.0, 2.0, 2.0],
                       true_output_tokens=[30, 60, 90])
    server.drain()
    assert rec.clock is clock
    arrival = dict(enumerate(arrivals + [2.0] * 3, start=1))
    timed = set()
    for resp in server.responses:
        rid = resp.request_id
        by = {s.name: s for s in rec.spans_for(rid)}
        if "feature_extract" in by:
            fx, pr = by["feature_extract"], by["predict"]
            assert fx.t0 == arrival[rid]
            assert fx.t0 <= fx.t1 <= pr.t0 <= pr.t1 < arrival[rid] + 0.01
            timed.add(rid)
        root = by["request"]
        assert root.t0 == arrival[rid]
        assert root.t1 == pytest.approx(arrival[rid] + resp.sojourn_s)
    assert timed == {1, 2, 3, 4, 5, 6, 7}     # one region per burst
    assert rec.validate(server._terminal) == []


@pytest.fixture(scope="module")
def small_predictor():
    from repro.core.gbdt import GBDTParams
    from repro.core.predictor import Predictor
    from repro.data.corpus import sample_dataset
    ds = sample_dataset("sharegpt", n=600, seed=42, balanced=True)
    return Predictor.train(ds.prompts, ds.lengths, GBDTParams(num_rounds=20))


# ----------------------------------------------------- DES-vs-live parity
def test_des_trace_schema_matches_sim_drain():
    """The DES post-processor and the server's virtual-time drain emit
    the same span vocabulary for the same workload."""
    model = ServiceTimeModel(prefill_tok_per_s=8000.0,
                             decode_tok_per_s=60.0)
    rng = np.random.default_rng(5)
    otoks = [int(rng.integers(20, 400)) for _ in range(20)]
    arrivals = sorted(float(rng.uniform(0, 5)) for _ in range(20))

    obs = Observability.default()
    server = ClairvoyantServer(policy="sjf_oracle", predictor=None,
                               service_model=model, seed=0,
                               observability=obs)
    reqs = [CompletionRequest(prompt=f"parity {i}") for i in range(20)]
    server.submit_many(reqs, arrivals=arrivals, true_output_tokens=otoks,
                       klasses=["short"] * 20)
    server.drain()

    des_rec = FlightRecorder()
    des_reqs = [Request(req_id=reqs[i].request_id, prompt=f"parity {i}",
                        arrival=arrivals[i],
                        true_service=model.service(
                            len(f"parity {i}".split()), otoks[i]),
                        meta={"output_tokens": otoks[i]})
                for i in range(20)]
    simulate(des_reqs, policy="sjf_oracle", recorder=des_rec)

    assert set(server.obs.recorder.schema()) == set(des_rec.schema())
    assert des_rec.validate([r.req_id for r in des_reqs],
                            [r.req_id for r in des_reqs]) == []


def _dispatch_order(rec, track="replica0"):
    pref = [s for s in rec.spans()
            if s.name == "prefill" and s.track == track]
    pref.sort(key=lambda s: s.t0)
    return [s.req_id for s in pref]


def test_des_and_live_wire_traces_match_at_c1():
    """A live loopback (sidecar) drain and a DES drain of the same
    workload export the same span schema and the same dispatch order at
    c=1 under the oracle SJF key."""
    from repro.serving.backends import HTTPBackend, SimTextBackend
    from repro.serving.http_sidecar import Sidecar

    model = ServiceTimeModel(prefill_tok_per_s=8000.0,
                             decode_tok_per_s=60.0)

    async def run():
        backend = SimTextBackend(model, replica_id=0, time_scale=0.05)
        srv = ClairvoyantServer(policy="sjf_oracle", predictor=None,
                                service_model=model, engines=[backend],
                                seed=0, deadline_mode="sojourn",
                                observability=Observability.default())
        sc = Sidecar(srv, port=0, max_new_tokens=512)
        await sc.start()
        client = HTTPBackend("127.0.0.1", sc.port)

        async def call(otok):
            payload = json.dumps(
                {"messages": [{"role": "user", "content": "same prompt"}],
                 "max_tokens": int(otok), "output_tokens": int(otok)}
            ).encode()
            r, w, status, _ = await client._request(
                "POST", "/v1/chat/completions", payload)
            doc = json.loads(await r.read(-1))
            w.close()
            assert status == 200
            return doc

        # the head request holds the serial lane long enough for the
        # rest to queue; the queue then drains in oracle-SJF order
        head = asyncio.create_task(call(200))
        await asyncio.sleep(0.08)
        rest = [asyncio.create_task(call(o)) for o in (32, 8, 24, 16, 40)]
        await asyncio.gather(head, *rest)
        await sc.shutdown(drain_s=2.0)
        return srv

    srv = asyncio.run(run())
    live_rec = srv.obs.recorder
    assert live_rec.validate(
        srv._terminal,
        [r.request_id for r in srv.responses if r.ok]) == []
    live_order = _dispatch_order(live_rec)
    assert len(live_order) == 6

    # rebuild the workload for the DES from the live trace: arrivals are
    # the queue_wait span starts, service the oracle key's service time
    arrival_of = {s.req_id: s.t0 for s in live_rec.spans()
                  if s.name == "queue_wait"}
    otok_of = {r.request_id: r.tokens_generated for r in srv.responses}
    ptoks = len("same prompt".split())
    des_rec = FlightRecorder()
    des_reqs = [Request(req_id=rid, prompt="same prompt",
                        arrival=arrival_of[rid],
                        true_service=model.service(ptoks, otok_of[rid]),
                        meta={"output_tokens": otok_of[rid]})
                for rid in live_order]
    simulate(des_reqs, policy="sjf_oracle", recorder=des_rec)

    assert set(des_rec.schema()) == set(live_rec.schema())
    assert _dispatch_order(des_rec) == live_order


# ------------------------------------------------------------ sidecar wire
def test_sidecar_metrics_healthz_readyz():
    from repro.serving.backends import HTTPBackend, SimTextBackend
    from repro.serving.http_sidecar import METRICS_CONTENT_TYPE, Sidecar

    model = ServiceTimeModel(prefill_tok_per_s=8000.0,
                             decode_tok_per_s=60.0)

    async def run():
        backends = [SimTextBackend(model, replica_id=i, time_scale=0.003)
                    for i in range(2)]
        srv = ClairvoyantServer(policy="sjf_oracle", predictor=None,
                                service_model=model, engines=backends,
                                seed=0, deadline_mode="sojourn",
                                breaker=CircuitBreaker())
        sc = Sidecar(srv, port=0, max_new_tokens=32)
        # no bundle attached: the sidecar builds the metrics+ranking
        # default (tracing off)
        assert srv.obs is not None and srv.obs.recorder is None
        await sc.start()
        client = HTTPBackend("127.0.0.1", sc.port)
        outs = await asyncio.gather(*[
            client.generate(f"prompt {i} " * (2 + i % 3),
                            max_new_tokens=8 + 4 * (i % 3))
            for i in range(8)])
        assert all(not o["cancelled"] for o in outs)

        r, w, status, hdrs = await client._request("GET", "/metrics")
        text = (await r.read(-1)).decode()
        w.close()
        assert status == 200
        assert hdrs.get("content-type") == METRICS_CONTENT_TYPE
        fams = parse_prometheus(text)          # raises on malformed lines
        assert "clairvoyant_terminals_total" in fams
        assert "clairvoyant_wire_total" in fams
        assert "clairvoyant_queue_depth" in fams
        term = sum(v for n, lab, v in fams["clairvoyant_terminals_total"]
                   if n.endswith("_total"))
        assert term == 8

        r, w, status, _ = await client._request("GET", "/healthz")
        doc = json.loads(await r.read(-1))
        w.close()
        assert status == 200
        assert [e["replica"] for e in doc["engines"]] == [0, 1]
        assert sum(e["served"] for e in doc["engines"]) == 8

        r, w, status, _ = await client._request("GET", "/readyz")
        doc = json.loads(await r.read(-1))
        w.close()
        assert status == 200 and doc["ready"]
        assert doc["ranking"]["recorded"] == 8
        assert all(rep["breaker"] == "closed" for rep in doc["replicas"])

        # the clairvoyant response block carries the ranking snapshot
        payload = json.dumps({"messages": [{"role": "user",
                                            "content": "once more"}],
                              "max_tokens": 8}).encode()
        r, w, status, _ = await client._request(
            "POST", "/v1/chat/completions", payload)
        doc = json.loads(await r.read(-1))
        w.close()
        assert "ranking" in doc["clairvoyant"]
        assert doc["clairvoyant"]["ranking"]["recorded"] >= 8
        await sc.shutdown(drain_s=2.0)

    asyncio.run(run())


def test_metrics_http_server_scrapes():
    from repro.serving.backends import HTTPBackend
    from repro.serving.metrics_http import CONTENT_TYPE, MetricsServer

    async def run():
        obs = Observability.default(tracing=False)
        obs.metrics.counter("clairvoyant_demo_total", "demo").inc(2)
        ms = MetricsServer(obs, port=0)
        await ms.start()
        client = HTTPBackend("127.0.0.1", ms.port)
        r, w, status, hdrs = await client._request("GET", "/metrics")
        text = (await r.read(-1)).decode()
        w.close()
        assert status == 200 and hdrs.get("content-type") == CONTENT_TYPE
        fams = parse_prometheus(text)
        assert fams["clairvoyant_demo_total"][0][2] == 2.0
        r, w, status, _ = await client._request("GET", "/nope")
        await r.read(-1)
        w.close()
        assert status == 404
        await ms.stop()

    asyncio.run(run())
