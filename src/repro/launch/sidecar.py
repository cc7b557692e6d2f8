"""Launch the HTTP/SSE sidecar: Clairvoyant behind a real socket.

    PYTHONPATH=src python -m repro.launch.sidecar --port 8080 \
        --backend sim --replicas 2 --policy sjf

then talk OpenAI chat-completions to it:

    curl -s localhost:8080/v1/chat/completions -d '{
        "messages": [{"role": "user", "content": "hello"}],
        "max_tokens": 32}'

Backends (one per replica):

* ``sim``  — virtual service times from the arch's ``ServiceTimeModel``,
  slept on the event loop and streamed as synthetic text
  (``--time-scale`` compresses wall time; the default for demos).
* ``real`` — an actual fused on-device decode per request
  (``RealEngine`` for ``--arch`` at its published widths, random
  weights from ``--seed``, a ``--max-len`` token window; off the event
  loop via a worker thread).  Replicas of the real backend all share
  one device.
* ``http`` — proxy to external OpenAI-compatible upstreams
  (``--upstream host:port``, repeatable), with connect/read timeouts
  feeding the retry policy and per-replica circuit breakers.

SIGINT/SIGTERM trigger a graceful drain: the listener closes, in-flight
work gets ``--drain-s`` seconds to finish, stragglers are cancelled at
the next segment boundary — every admitted request still leaves with
exactly one terminal status.

Observability (PR 10): the main port always serves Prometheus text on
``GET /metrics``; ``--metrics-port`` additionally exposes it on a
dedicated scrape port (so load balancers need not route scrapes through
the serving listener).  ``--trace-out FILE`` enables the flight
recorder and writes a Chrome/Perfetto ``trace_event`` JSON of every
request's span timeline at shutdown; ``--log-json FILE`` writes the
same spans as structured JSONL.
"""

from __future__ import annotations

import argparse
import asyncio
import signal

from repro.configs import get_config
from repro.core.calibration import calibrate_tau
from repro.core.simulation import ServiceDist
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.serve import build_predictor
from repro.serving.faults import CircuitBreaker, FaultPlan, RetryPolicy
from repro.serving.http_sidecar import Sidecar
from repro.serving.server import ClairvoyantServer
from repro.serving.service_time import ServiceTimeModel


def build_sidecar(args) -> Sidecar:
    cfg = get_config(args.arch)
    model = ServiceTimeModel.from_arch(cfg, chips=args.chips)
    if getattr(args, "speculative", False):
        # mirror draft-verify decode in the cost model (and therefore in
        # the tau calibration below): decode runs at the expected
        # speculative speedup of the assumed acceptance rate
        from dataclasses import replace as _replace

        from repro.serving.service_time import expected_speedup
        model = _replace(model, effective_rate=float(
            expected_speedup(args.accept_rate, args.draft_k)))
    from repro.core.policy import get_policy
    predictor = build_predictor(args.dataset) \
        if get_policy(args.policy).uses_predictor and not args.no_predictor \
        else None
    short_dist = ServiceDist(model.service(64, 60),
                             0.3 * model.service(64, 60))
    long_dist = ServiceDist(model.service(64, 1400),
                            0.3 * model.service(64, 1400))
    tau = calibrate_tau(short_dist, long_dist, multiplier=args.tau_mult)

    if args.backend == "sim":
        from repro.serving.backends import SimTextBackend
        backends = [SimTextBackend(model, replica_id=i,
                                   time_scale=args.time_scale)
                    for i in range(args.replicas)]
    elif args.backend == "real":
        from repro.serving.backends import InProcessBackend
        from repro.serving.engine import RealEngine
        spec_kw = {}
        if getattr(args, "speculative", False):
            dcfg = get_config(args.draft_model) \
                if args.draft_model else cfg
            spec_kw = dict(draft_cfg=dcfg, draft_k=args.draft_k,
                           draft_seed=args.seed)
        backends = [InProcessBackend(RealEngine(cfg, seed=args.seed,
                                                max_len=args.max_len,
                                                **spec_kw))
                    for _ in range(args.replicas)]
        for i, b in enumerate(backends):
            b.replica_id = i
    else:                                    # http: proxy to upstreams
        from repro.serving.backends import HTTPBackend
        if not args.upstream:
            raise SystemExit("--backend http requires --upstream host:port")
        backends = []
        for i, up in enumerate(args.upstream):
            host, _, port = up.partition(":")
            backends.append(HTTPBackend(host, int(port or 80),
                                        replica_id=i, model=args.model))

    fault_plan = FaultPlan.random(
        seed=args.seed, horizon=3600.0, n_replicas=len(backends),
        crash_mtbf=args.chaos_crash_mtbf or None,
        transient_rate=args.chaos_transient_rate or None) \
        if args.chaos_crash_mtbf or args.chaos_transient_rate else None

    server = ClairvoyantServer(
        policy=args.policy, tau=tau, predictor=predictor,
        service_model=model, engines=backends, seed=args.seed,
        fault_plan=fault_plan, retry=RetryPolicy(seed=args.seed),
        deadline_s=args.deadline_s, deadline_mode="sojourn",
        max_queue_depth=args.max_queue_depth,
        breaker=CircuitBreaker(recovery_s=args.breaker_recovery_s))
    if getattr(args, "trace_out", None) or getattr(args, "log_json", None):
        # tracing requested: attach a full bundle (recorder + metrics +
        # ranking) before the Sidecar builds its metrics-only default
        from repro.serving.observability import Observability
        server.attach_observability(Observability.default(tracing=True))
    return Sidecar(server, host=args.host, port=args.port,
                   model=args.model, max_inflight=args.max_inflight,
                   tenant_rate=args.tenant_rate,
                   tenant_burst=args.tenant_burst,
                   drain_s=args.drain_s,
                   max_new_tokens=args.max_new_tokens)


async def serve(args) -> None:
    sidecar = build_sidecar(args)
    await sidecar.start()
    print(f"sidecar listening on {sidecar.address} "
          f"(policy={args.policy}, backend={args.backend}, "
          f"replicas={len(sidecar.backends)})", flush=True)
    metrics_srv = None
    if getattr(args, "metrics_port", None) is not None:
        from repro.serving.metrics_http import MetricsServer
        metrics_srv = MetricsServer(sidecar.obs, host=args.host,
                                    port=args.metrics_port)
        await metrics_srv.start()
        print(f"metrics on http://{args.host}:{metrics_srv.port}/metrics",
              flush=True)
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop.set)
        except NotImplementedError:          # non-unix
            pass
    await stop.wait()
    print("draining...", flush=True)
    await sidecar.shutdown()
    if metrics_srv is not None:
        await metrics_srv.stop()
    rec = sidecar.obs.recorder
    if rec is not None:
        if getattr(args, "trace_out", None):
            rec.write_perfetto(args.trace_out)
            print(f"perfetto trace ({len(rec)} spans) -> {args.trace_out}",
                  flush=True)
        if getattr(args, "log_json", None):
            rec.write_jsonl(args.log_json)
            print(f"span JSONL -> {args.log_json}", flush=True)
    srv = sidecar.server
    done = len(srv.responses)
    ok = sum(1 for r in srv.responses if r.ok)
    print(f"drained: {done} terminals ({ok} ok), "
          f"fault_stats={srv.fault_stats}, "
          f"wire_stats={sidecar.wire_stats}", flush=True)


def build_parser() -> argparse.ArgumentParser:
    """The sidecar's command line (``main`` parses it; ``chip_smoke.py``
    builds its sidecar through it)."""
    from repro.core.policy import registered_names
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080)
    ap.add_argument("--policy", default="sjf",
                    choices=sorted(registered_names()))
    ap.add_argument("--backend", default="sim",
                    choices=("sim", "real", "http"))
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--upstream", action="append", default=[],
                    help="host:port of an OpenAI-compatible upstream "
                         "(repeat for multiple replicas; --backend http)")
    ap.add_argument("--model", default="clairvoyant-sim")
    ap.add_argument("--arch", default="gemma3-4b-edge")
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--max-len", type=int, default=2048,
                    help="real backend: KV window (prompt + generated "
                         "tokens) per request")
    ap.add_argument("--dataset", default="sharegpt")
    ap.add_argument("--no-predictor", action="store_true")
    ap.add_argument("--tau-mult", type=float, default=3.0)
    ap.add_argument("--time-scale", type=float, default=0.02,
                    help="sim backend: wall seconds per virtual second")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="server-wide sojourn deadline (per-request "
                         "X-Deadline-S overrides)")
    ap.add_argument("--max-queue-depth", type=int, default=None)
    ap.add_argument("--max-inflight", type=int, default=256)
    ap.add_argument("--max-new-tokens", type=int, default=64)
    ap.add_argument("--tenant-rate", type=float, default=None,
                    help="per-tenant token-bucket rate (req/s); "
                         "unset = no rate limiting")
    ap.add_argument("--tenant-burst", type=float, default=10.0)
    ap.add_argument("--drain-s", type=float, default=30.0)
    ap.add_argument("--breaker-recovery-s", type=float, default=5.0)
    ap.add_argument("--speculative", action="store_true",
                    help="draft-verify decode: the real backend runs a "
                         "draft model per replica; the sim backend (and "
                         "the tau calibration) apply the expected "
                         "speculative speedup to the service-time model")
    ap.add_argument("--draft-model", default=None,
                    help="draft arch name (default: the target arch "
                         "itself — 100%% acceptance sanity mode)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="draft tokens proposed per verify step")
    ap.add_argument("--accept-rate", type=float, default=0.7,
                    help="assumed draft acceptance rate for the "
                         "service-time mirror (sim backend/calibration)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="also serve Prometheus /metrics on this "
                         "dedicated port (0 = ephemeral); the main port "
                         "serves /metrics regardless")
    ap.add_argument("--trace-out", default=None,
                    help="enable the flight recorder and write a "
                         "Chrome/Perfetto trace_event JSON of every "
                         "request's span timeline here at shutdown")
    ap.add_argument("--log-json", default=None,
                    help="enable the flight recorder and write the span "
                         "log as structured JSONL here at shutdown")
    ap.add_argument("--chaos-crash-mtbf", type=float, default=0.0,
                    help=">0: inject engine crashes at this MTBF (s)")
    ap.add_argument("--chaos-transient-rate", type=float, default=0.0,
                    help=">0: injected transient errors per second")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    setup_compile_cache()
    asyncio.run(serve(args))


if __name__ == "__main__":
    main()
