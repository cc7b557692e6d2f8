"""The Clairvoyant sidecar: features -> predictor -> SJF queue -> engine.

This is the paper's Figure 2 as framework code.  ``ClairvoyantServer``
fronts N replica engines; each replica is a serial backend with its own
SJFQueue (+ starvation guard).  The multi-replica case routes by predicted
work (core/router.py, beyond paper).  The scheduling policy is a
first-class ``core.policy.Policy`` (registry name or instance): the seed
"fcfs" / "sjf" / "sjf_oracle" plus preemptive SRPT, quantile-aware SJF,
MLFQ and per-tenant fair share — the benchmark ablation is one
constructor argument.  Preemptive policies evict the running request at
the next fused-decode segment boundary (real engines: cancel + resume by
re-prefilling prompt + generated prefix; sim engines: the preemptive DES
in virtual time).

Two backends share the queueing layer:

* the default ``SimEngine`` fleet serves in virtual time from a
  ``ServiceTimeModel`` (thousands of requests, the queueing benchmarks);
* passing ``engines=[RealEngine(...), ...]`` serves each dispatched request
  with an actual fused on-device decode (serving/engine.py) and measured
  wall-clock service times — the end-to-end path the serve benchmark
  exercises (predictor -> SJF queue -> real decode);
* passing ``engines=[BatchedRealEngine(...)]`` drains the queue through
  bounded-concurrency decode lanes under a KV-memory budget
  (``_drain_batched``): back-fill pops via ``SJFQueue.pop_many`` so
  aging promotions are observed between pops, admission blocks on the
  budget in strict policy order, and client disconnects evict their
  lane at the next segment boundary.  Preemptive policies use the
  serial drain (lane eviction by key is future work).

Admission is batched: ``submit_many`` runs feature extraction + GBDT
prediction once across an arrival burst (the PR 1 ``proba_batch`` fast
path); ``submit`` is the single-request convenience wrapper over the same
``_admit``.

The virtual-clock drain loop is event-driven: at every dispatch decision the
queue applies the starvation check, exactly like the Go dispatcher goroutine.
Mid-generation disconnects on a real backend go through ``cancel``: if the
request is currently decoding, the engine's cancel flag stops the fused loop
at the next segment boundary (§3.4 drain semantics).

Robustness (PR 6) — the drain loops are exception-safe and every
submitted request terminates with exactly one terminal
``CompletionResponse`` (``ok | shed | failed | timeout | cancelled``),
the **no-lost-requests invariant** (enforced: a second terminal response
for the same request raises).  The pieces:

* ``fault_plan`` — a seeded ``serving.faults.FaultPlan`` injects engine
  crashes (virtual-time for sim drains, fused-decode segment boundaries
  for real engines), straggler stall windows, transient backend errors,
  predictor outages and admission-overflow windows.
* engine faults (injected or organic ``Exception`` from an engine call)
  requeue the in-flight request with its original arrival (sojourn
  accounting is preserved) under a jittered-exponential ``RetryPolicy``;
  retries exhausted => terminal ``failed`` response, never a raise.
* ``deadline_s`` — per-request queue-wait budget: a request still
  undispatched past its budget is shed at dispatch time (terminal
  ``shed`` response), bounding tail latency under overload.
* graceful predictor degradation — a predictor exception, NaN scores,
  or an injected outage flips the server into degraded mode
  (``self.degraded``): admission continues with ``p_long = 0`` for
  every request, which collapses SJF to FCFS (equal keys -> FIFO
  tie-break), and recovers as soon as a later predictor call succeeds.
* per-replica circuit breaker (``breaker=``) — consecutive recorded
  failures stop placement on a replica until a half-open probe succeeds
  (core/router.py).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.core.policy import get_policy
from repro.core.predictor import Predictor
from repro.core.router import PredictiveRouter
from repro.core.scheduler import Request, SJFQueue
from repro.serving.engine import BatchedRealEngine, RealEngine, SimEngine
from repro.serving.faults import (CircuitBreaker, EngineCrash, FaultError,
                                  RetryPolicy, TransientBackendError,
                                  as_injector)
from repro.serving.observability import (NO_REGION, Observability,
                                         anchored_clock,
                                         record_service_spans)
from repro.serving.openai_api import CompletionRequest, CompletionResponse
from repro.serving.service_time import ServiceTimeModel, sample_output_tokens
from repro.data.tokenizer import HashTokenizer, approx_token_len
from repro.serving.backends import tokens_to_text


class ClairvoyantServer:
    def __init__(self, *, policy="sjf", tau: Optional[float] = None,
                 n_replicas: int = 1,
                 predictor: Optional[Predictor] = None,
                 service_model: Optional[ServiceTimeModel] = None,
                 engines: Optional[Sequence] = None,
                 seed: int = 0,
                 fault_plan=None,
                 retry: Optional[RetryPolicy] = None,
                 deadline_s: Optional[float] = None,
                 deadline_mode: str = "queue",
                 max_queue_depth: Optional[int] = None,
                 breaker: Optional[CircuitBreaker] = None,
                 observability: Optional[Observability] = None):
        # policy: registry name or Policy instance (core/policy.py)
        self.policy_obj = get_policy(policy)
        self.policy = self.policy_obj.name
        self.predictor = predictor
        self.rng = np.random.default_rng(seed)
        self.service_model = service_model or ServiceTimeModel(
            prefill_tok_per_s=8000.0, decode_tok_per_s=60.0)
        if engines is not None:
            self.engines = list(engines)
            n_replicas = len(self.engines)
        else:
            self.engines = [SimEngine(self.service_model, i)
                            for i in range(n_replicas)]
        self.router = PredictiveRouter(n_replicas, policy=policy, tau=tau,
                                       breaker=breaker)
        self._inflight: Dict[int, CompletionRequest] = {}
        self._decoding: Dict[int, int] = {}     # replica_id -> request_id
        self._disconnected: set = set()         # mid-flight client cancels
        self._oracle_tokens: Dict[int, int] = {}
        self._tokenizer: Optional[HashTokenizer] = None
        self.responses: List[CompletionResponse] = []
        # --- robustness layer (serving/faults.py) ---
        self.faults = as_injector(fault_plan)
        self.retry = retry if retry is not None else RetryPolicy(seed=seed)
        self.deadline_s = deadline_s
        # "queue" (PR 6): deadline bounds QUEUE WAIT only — undispatched
        # work is shed, started work always completes.  "sojourn": the
        # deadline bounds arrival-to-finish — pre-dispatch expiry still
        # sheds, but expiry MID-SERVICE terminates with status "timeout"
        # (the wire semantics the async sidecar exposes).
        if deadline_mode not in ("queue", "sojourn"):
            raise ValueError(f"unknown deadline_mode {deadline_mode!r}")
        self.deadline_mode = deadline_mode
        self.max_queue_depth = max_queue_depth
        self.degraded = False                   # predictor-outage FCFS mode
        self._terminal: Dict[int, str] = {}     # req_id -> terminal status
        self._next_id = 1                       # per-server request-id space
        self.fault_stats = {"predictor_failures": 0,
                            "degraded_admissions": 0, "sheds": 0,
                            "retries": 0, "failures": 0, "crashes": 0,
                            "transients": 0, "requeues": 0, "timeouts": 0}
        if self.faults is not None:
            for eng in self.engines:
                if isinstance(eng, RealEngine):
                    eng.fault_injector = self.faults
        # --- observability (serving/observability.py) ---
        # self.obs is read per call site (``obs = self.obs``) so a sidecar
        # may attach one after construction; every hook is gated on the
        # component being present (zero cost when disabled).
        self.obs: Optional[Observability] = None
        self._obs_arrival: Dict[int, float] = {}   # req_id -> arrival time
        if observability is not None:
            self.attach_observability(observability)

    def attach_observability(self, obs: Observability) -> None:
        """Wire the flight recorder + metrics registry into the stack:
        the router's route-decision instants, the batched engines' lane
        spans, and the scrape-time collectors over stats the server and
        engines already keep."""
        self.obs = obs
        self.router.recorder = obs.recorder
        for eng in self.engines:
            if hasattr(eng, "recorder"):
                eng.recorder = obs.recorder
        obs.register_server(self)
        obs.register_engines(self.engines)

    # ------------------------------------------------------------------ API
    def _predict_probas(self, prompts: List[str], now: float,
                        rid_hint: Optional[int] = None):
        """Predictor call with graceful degradation: an exception, a
        non-finite score, or an injected outage window returns None (the
        caller admits with ``p_long = 0`` for all — FCFS order) and flips
        ``self.degraded``; a later successful call heals the server back
        to predictive SJF.  Never raises to the submitting client.

        When a flight recorder is attached, the two admission stages are
        timed separately (``feature_extract`` / ``predict`` regions on
        the caller's timeline: from the arrival instant ``now``, which is
        the sidecar's clock when live, on with wall time) and the
        per-request predictor latency feeds its histogram — the paper's
        0.029 ms claim, observable on live traffic."""
        if self.predictor is None or not self.policy_obj.uses_predictor \
                or not prompts:
            return None
        obs = self.obs
        rec = obs.recorder if obs is not None else None
        probas = None
        if self.faults is None or not self.faults.predictor_down(now):
            try:
                if obs is not None and isinstance(self.predictor, Predictor):
                    import time as _time
                    from repro.core import features as _F
                    rid = rid_hint if rid_hint is not None else self._next_id
                    trk = None if rec is None else f"req{rid}"
                    clk = None if rec is None else anchored_clock(now)
                    n = len(prompts)
                    w0 = _time.perf_counter()
                    with NO_REGION if rec is None else rec.region(
                            "feature_extract", rid, trk, clock=clk, batch=n):
                        X = _F.extract_batch(prompts)
                    with NO_REGION if rec is None else rec.region(
                            "predict", rid, trk, clock=clk, batch=n):
                        probas = np.asarray(
                            self.predictor.model.predict_proba(X), float)
                    obs.observe_predict(n, _time.perf_counter() - w0)
                else:
                    probas = np.asarray(
                        self.predictor.proba_batch(prompts), float)
                if not np.all(np.isfinite(probas)):
                    probas = None                # NaN/inf scores: degrade
            except Exception:
                probas = None                    # predictor raised: degrade
        if probas is None:
            self.fault_stats["predictor_failures"] += 1
            self.degraded = True
            return None
        self.degraded = False                    # predictor healed
        return probas

    def allocate_id(self) -> int:
        """Reserve the next request id from this server's id space (the
        sidecar pre-assigns ids so it can register a waiter before the
        admission path can emit a terminal shed response)."""
        rid = self._next_id
        self._next_id += 1
        return rid

    def submit(self, req: CompletionRequest, *, arrival: float = 0.0,
               true_output_tokens: Optional[int] = None,
               klass: str = "", deadline_s: Optional[float] = None) -> int:
        """Admit one request.  ``true_output_tokens`` is the oracle ground
        truth (known to the simulator, NOT the scheduler unless policy is
        sjf_oracle).  ``deadline_s`` overrides the server-wide budget for
        this request.  Returns the chosen replica, or -1 if the request
        was shed at admission (queue overflow)."""
        probas = self._predict_probas([req.prompt], arrival,
                                      rid_hint=req.request_id)
        return self._admit(req, None if probas is None else probas[0],
                           arrival, true_output_tokens, klass,
                           deadline_s=deadline_s)

    def submit_many(self, reqs: Sequence[CompletionRequest], *,
                    arrivals: Optional[Sequence[float]] = None,
                    true_output_tokens: Optional[Sequence[int]] = None,
                    klasses: Optional[Sequence[str]] = None) -> List[int]:
        """Admit an arrival burst with ONE batched predictor call.

        Feature extraction + GBDT scoring run once over the whole batch
        (``Predictor.proba_batch``, the PR 1 vectorized admission fast
        path) instead of once per request — ~10x cheaper per request at
        realistic burst sizes.  Returns the chosen replica per request
        (-1 for requests shed at admission).
        """
        n = len(reqs)
        probas = self._predict_probas(
            [r.prompt for r in reqs],
            0.0 if arrivals is None or not n else float(arrivals[0]),
            rid_hint=reqs[0].request_id if n else None)
        return [
            self._admit(
                req,
                None if probas is None else probas[i],
                0.0 if arrivals is None else float(arrivals[i]),
                None if true_output_tokens is None else int(true_output_tokens[i]),
                "" if klasses is None else klasses[i])
            for i, req in enumerate(reqs)
        ]

    def _admit(self, req: CompletionRequest, proba, arrival: float,
               true_output_tokens: Optional[int], klass: str,
               deadline_s: Optional[float] = None) -> int:
        # per-server id space: assign at admission (dense, deterministic
        # per server); explicit ids are honored but may not collide with
        # a request this server has already seen
        if req.request_id is None:
            req.request_id = self.allocate_id()
        else:
            self._next_id = max(self._next_id, int(req.request_id) + 1)
        if req.request_id in self._terminal \
                or req.request_id in self._inflight:
            raise ValueError(f"request id {req.request_id} already "
                             "submitted to this server")
        obs = self.obs
        if obs is not None:
            # arrival anchors the root "request" span emitted at _finish
            self._obs_arrival[req.request_id] = arrival
            obs.observe_admission(1, self.policy)
        if true_output_tokens is None:
            true_output_tokens = sample_output_tokens(
                self.rng, klass or "short")
        prompt_toks = approx_token_len(req.prompt)
        p_long = float(proba[2]) if proba is not None else 0.0
        degraded = proba is None and self.degraded \
            and self.policy_obj.uses_predictor
        r = Request(req_id=req.request_id, prompt=req.prompt, arrival=arrival,
                    p_long=p_long, klass=klass,
                    true_service=self.service_model.service(
                        prompt_toks, true_output_tokens),
                    tenant=req.tenant,
                    meta={"prompt_tokens": prompt_toks,
                          "output_tokens": true_output_tokens})
        if deadline_s is not None:
            r.meta["deadline_s"] = float(deadline_s)
        if degraded:
            r.meta["degraded"] = True
            self.fault_stats["degraded_admissions"] += 1
        # bounded admission queue / injected overflow window: shed, never
        # enqueue-and-forget
        depth = sum(len(rep.queue) for rep in self.router.replicas)
        if (self.max_queue_depth is not None
                and depth >= self.max_queue_depth) \
                or (self.faults is not None
                    and self.faults.overflow_active(arrival)):
            self.fault_stats["sheds"] += 1
            self._finish(CompletionResponse(
                request_id=req.request_id, text="", tokens_generated=0,
                queue_wait_s=0.0, service_s=0.0, replica=-1,
                p_long=p_long, klass=klass, status="shed",
                error="admission queue overflow", degraded=degraded))
            return -1
        self._inflight[req.request_id] = req
        self._oracle_tokens[req.request_id] = true_output_tokens
        return self.router.route(r, proba=proba, now=arrival)

    # -------------------------------------------------------- terminal path
    def _finish(self, resp: CompletionResponse) -> None:
        """The single exit gate: every submitted request passes through
        here exactly once (the no-lost-requests invariant — a duplicate
        terminal response is a scheduler bug and raises)."""
        prev = self._terminal.get(resp.request_id)
        if prev is not None:
            raise RuntimeError(
                f"request {resp.request_id} already terminated "
                f"({prev!r}); duplicate terminal status {resp.status!r}")
        self._terminal[resp.request_id] = resp.status
        self._inflight.pop(resp.request_id, None)
        self.responses.append(resp)
        obs = self.obs
        if obs is not None:
            obs.observe_terminal(
                resp, self._obs_arrival.pop(resp.request_id, None))

    def _deadline_of(self, req) -> Optional[float]:
        """Effective deadline budget for one request: the per-request
        override (``submit(..., deadline_s=)``) or the server-wide one."""
        return req.meta.get("deadline_s", self.deadline_s)

    def _maybe_shed(self, rep, req, now: float) -> bool:
        """Deadline-budget load shedding at dispatch time: a request that
        has not started and is already past its queue-wait budget is shed
        with a terminal response (bounds the tail under overload)."""
        dl = self._deadline_of(req)
        if dl is None or req.start is not None \
                or (now - req.arrival) <= dl:
            return False
        self.router.release(rep.replica_id, req)
        self.fault_stats["sheds"] += 1
        req.finish = now
        obs = self.obs
        if obs is not None and obs.recorder is not None:
            obs.recorder.span("queue_wait", req.req_id, req.arrival, now,
                              track=f"req{req.req_id}")
        self._finish(CompletionResponse(
            request_id=req.req_id, text="", tokens_generated=0,
            queue_wait_s=max(0.0, now - req.arrival), service_s=0.0,
            replica=rep.replica_id, p_long=req.p_long, klass=req.klass,
            status="shed", error="deadline budget exceeded before dispatch",
            retries=req.meta.get("fault_retries", 0),
            degraded=bool(req.meta.get("degraded"))))
        return True

    def _retry_or_fail(self, rep, req, now: float, exc: Exception,
                       charge_backoff: bool = True) -> float:
        """Shared fault epilogue for all drain loops: the popped request
        either re-enters its queue (bounded retries, original arrival
        preserved) or terminates with a ``failed`` response.  Returns the
        (possibly backoff-advanced) clock."""
        n = req.meta.get("fault_retries", 0) + 1
        req.meta["fault_retries"] = n
        self.router.record_failure(rep.replica_id, now)
        if isinstance(exc, EngineCrash):
            self.fault_stats["crashes"] += 1
        elif isinstance(exc, TransientBackendError):
            self.fault_stats["transients"] += 1
        if n > self.retry.max_retries:
            self.fault_stats["failures"] += 1
            self.router.release(rep.replica_id, req)
            start = req.start if req.start is not None else now
            req.finish = now
            self._finish(CompletionResponse(
                request_id=req.req_id, text="", tokens_generated=0,
                queue_wait_s=max(0.0, start - req.arrival),
                service_s=max(0.0, now - start),
                replica=rep.replica_id, p_long=req.p_long, klass=req.klass,
                status="failed", error=f"{type(exc).__name__}: {exc}",
                retries=n, degraded=bool(req.meta.get("degraded"))))
            return now
        self.fault_stats["retries"] += 1
        self.fault_stats["requeues"] += 1
        if charge_backoff:
            now += self.retry.backoff(n - 1)
        rep.queue.push_requeue(
            req, req.meta.get("queue_key",
                              req.meta.get("policy_key0", 0.0)),
            reason="fault")
        return now

    def cancel(self, request_id: int) -> bool:
        """Client disconnect: lazy-delete from whichever queue holds it; if
        it is mid-generation on a real engine, flag the fused loop to drain
        at the next segment boundary.  A queued cancel terminates the
        request immediately with a ``cancelled`` response; a mid-flight
        cancel terminates when the drain loop observes the eviction —
        either way the request is never silently dropped."""
        for rep in self.router.replicas:
            req = rep.queue._live.get(request_id)
            if rep.queue.cancel(request_id):
                self.router.release(rep.replica_id, req)
                self._finish(CompletionResponse(
                    request_id=request_id, text="", tokens_generated=0,
                    queue_wait_s=0.0, service_s=0.0,
                    replica=rep.replica_id,
                    p_long=req.p_long, klass=req.klass,
                    status="cancelled", error="client disconnect (queued)",
                    degraded=bool(req.meta.get("degraded"))))
                return True
        for eng in self.engines:
            # mid-flight on a batched engine: flag the lane; the drain
            # loop evicts it at the next segment boundary
            if isinstance(eng, BatchedRealEngine) \
                    and eng.lane_manager is not None \
                    and eng.lane_manager.lane_of(request_id) is not None:
                self._disconnected.add(request_id)
                return True
        for replica_id, rid in self._decoding.items():
            if rid == request_id:
                eng = self.engines[replica_id]
                if hasattr(eng, "request_cancel"):
                    # distinguishes a disconnect from a preemption eviction:
                    # the drain loop drops disconnected requests instead of
                    # re-enqueueing them
                    self._disconnected.add(request_id)
                    eng.request_cancel()
                    return True
        return False

    def drain(self, max_new_tokens: int = 64) -> List[CompletionResponse]:
        """Run every replica's serial loop to completion.

        SimEngine replicas advance a virtual clock from the service-time
        model; RealEngine replicas actually decode each request (fused loop)
        and feed the measured wall-clock service time into the same clock.
        """
        for rep, eng in zip(self.router.replicas, self.engines):
            if isinstance(eng, BatchedRealEngine) \
                    and not self.policy_obj.preemptive:
                self._drain_batched(rep, eng, max_new_tokens)
            elif isinstance(eng, RealEngine):
                self._drain_real(rep, eng, max_new_tokens)
            else:
                self._drain_sim(rep, eng)
        return self.responses

    def _drain_sim(self, rep, eng) -> None:
        """Virtual-clock serial drain, exception-safe: every popped
        request terminates through ``_finish`` (ok / shed / failed) or
        re-enters the queue — injected faults (transient errors, stalls,
        crash + repair) and organic engine exceptions both route through
        ``_retry_or_fail``.  The loop always re-pops, so a requeued
        request is served later in this same drain."""
        if self.policy_obj.preemptive:
            self._drain_sim_preemptive(rep, eng)
            return
        inj = self.faults
        rid = rep.replica_id
        obs = self.obs
        rec = obs.recorder if obs is not None else None
        trk = f"replica{rid}"
        t = eng.busy_until
        while True:
            req = rep.queue.pop(now=t)
            if req is None:
                break
            t = max(t, req.arrival)
            if self._maybe_shed(rep, req, t):
                continue
            # injected transient backend error: fails this attempt before
            # any service is rendered
            if inj is not None:
                spec = inj.transient_due(rid, t)
                if spec is not None:
                    t = self._retry_or_fail(rep, req, t,
                                            TransientBackendError(
                                                "injected transient "
                                                "backend error"))
                    continue
            if req.start is None:
                req.start = t                  # first dispatch
            try:
                ttft, service = self._sim_execute(eng, rid, t, req)
            except FaultError as e:
                # engine crash mid-service: the clock is already advanced
                # to the end of the repair window by _sim_execute
                t = self._retry_or_fail(rep, req, eng.busy_until, e,
                                        charge_backoff=False)
                continue
            except Exception as e:             # organic engine bug
                t = self._retry_or_fail(rep, req, t, e)
                continue
            if self.deadline_mode == "sojourn":
                dl = self._deadline_of(req)
                if dl is not None and t + service > req.arrival + dl:
                    # in-service expiry: the attempt is abandoned AT the
                    # deadline instant with a terminal ``timeout`` (the
                    # pre-dispatch case stays ``shed`` via _maybe_shed)
                    expiry = max(t, req.arrival + dl)
                    eng.busy_until = expiry
                    self.router.release(rid, req)
                    self.fault_stats["timeouts"] += 1
                    req.finish = expiry
                    if rec is not None:
                        record_service_spans(
                            rec, req.req_id, arrival=req.arrival,
                            start=t, finish=expiry,
                            ttft=min(ttft, expiry - t),
                            out_tokens=req.meta["output_tokens"],
                            track=trk)
                    self._finish(CompletionResponse(
                        request_id=req.req_id, text="", tokens_generated=0,
                        queue_wait_s=req.start - req.arrival,
                        service_s=max(0.0, expiry - req.start),
                        ttft_s=(req.start - req.arrival + ttft)
                        if t + ttft <= expiry else None,
                        promoted=req.promoted, replica=rid,
                        p_long=req.p_long, klass=req.klass,
                        status="timeout",
                        error="deadline expired in service",
                        retries=req.meta.get("fault_retries", 0),
                        degraded=bool(req.meta.get("degraded"))))
                    t = expiry
                    continue
            t += service
            req.finish = t
            self.router.on_dispatch(rid, req, t, service_estimate=service)
            self.router.record_success(rid, t)
            retries = req.meta.get("fault_retries", 0)
            if rec is not None:
                record_service_spans(
                    rec, req.req_id, arrival=req.arrival,
                    start=t - service, finish=t, ttft=ttft,
                    out_tokens=req.meta["output_tokens"], track=trk)
            self._finish(CompletionResponse(
                request_id=req.req_id, text="",
                tokens_generated=req.meta["output_tokens"],
                queue_wait_s=req.start - req.arrival,
                # a fault-requeued request reports time-in-service across
                # the gaps so sojourn_s == finish - arrival stays exact
                service_s=service if retries == 0 else t - req.start,
                ttft_s=req.start - req.arrival + ttft,
                promoted=req.promoted, replica=rid,
                p_long=req.p_long, klass=req.klass, retries=retries,
                degraded=bool(req.meta.get("degraded"))))

    def _sim_execute(self, eng, rid: int, t: float, req) -> tuple:
        """One virtual-time service attempt with fault injection.  Returns
        ``(ttft, service)`` and advances the engine clock on success; on
        an injected crash raises :class:`EngineCrash` with the engine
        parked at the end of its repair window and the request's partial
        progress recorded (work-conserving requeue: the next attempt only
        serves the remaining work)."""
        ptoks = req.meta["prompt_tokens"]
        otoks = req.meta["output_tokens"]
        full = eng.model.service(ptoks, otoks)
        used = req.meta.get("sim_used_s", 0.0)
        rem = max(full - used, 0.0)
        inj = self.faults
        if inj is not None:
            rem *= inj.stall_factor(rid, t)    # straggler window
            crash = inj.crash_between(rid, t, t + rem)
            if crash is not None:
                crash_t = max(t, crash.at)
                req.meta["sim_used_s"] = used + (crash_t - t)
                eng.busy_until = crash_t + crash.repair_s
                raise EngineCrash("injected engine crash mid-service",
                                  at=crash_t, repair_s=crash.repair_s)
        ttft = eng.model.overhead_s + ptoks / eng.model.prefill_tok_per_s
        eng.busy_until = t + rem
        eng.served += 1
        return ttft, rem

    def _drain_sim_preemptive(self, rep, eng) -> None:
        """Virtual-time drain under a preemptive policy: the replica's
        whole backlog runs through the preemptive DES engine (arrival
        events slice service; evicted work is re-enqueued with the
        policy's requeue key), then responses are emitted in finish
        order.  ``queue_wait_s`` is time to FIRST dispatch."""
        from repro.core.sim_fast import RequestBatch, simulate_batch
        reqs = rep.queue.waiting()
        for r in reqs:                       # drain the queue bookkeeping
            rep.queue.remove(r.req_id)
            rep.queue.stats["dispatched"] += 1
        if not reqs:
            return
        batch = RequestBatch.from_requests(reqs)
        # the engine may still be busy from a previous drain: nothing can
        # start before busy_until, so clamp the simulated arrivals (waits
        # are still reported against the TRUE arrival, like _drain_sim)
        batch.arrival = np.maximum(batch.arrival, eng.busy_until)
        res = simulate_batch(batch, policy=self.policy_obj,
                             tau=rep.queue.tau)
        rep.queue.stats["promotions"] += res.promotions
        rep.queue.stats["preemptions"] += res.preemptions
        obs = self.obs
        rec = obs.recorder if obs is not None else None
        order = np.argsort(res.finish, kind="stable")
        for i in order:
            req = reqs[i]
            req.start = float(res.start[i])
            req.finish = float(res.finish[i])
            req.promoted = bool(res.promoted[i])
            service = req.true_service
            ttft = (eng.model.overhead_s + req.meta["prompt_tokens"]
                    / eng.model.prefill_tok_per_s)
            if rec is not None:
                # preempted services interleave, so [start, finish]
                # windows of different requests can partially overlap:
                # each request gets its own sub-track of the replica
                record_service_spans(
                    rec, req.req_id, arrival=req.arrival, start=req.start,
                    finish=req.finish, ttft=ttft,
                    out_tokens=req.meta["output_tokens"],
                    track=f"replica{rep.replica_id}/req{req.req_id}")
            eng.busy_until = max(eng.busy_until, req.finish)
            eng.served += 1
            self.router.on_dispatch(rep.replica_id, req, req.finish,
                                    service_estimate=service)
            self._finish(CompletionResponse(
                request_id=req.req_id, text="",
                tokens_generated=req.meta["output_tokens"],
                queue_wait_s=req.start - req.arrival,
                # time in service INCLUDING eviction gaps, so sojourn_s
                # (wait + service) equals finish - arrival exactly
                service_s=req.finish - req.start,
                ttft_s=req.start - req.arrival + ttft,
                promoted=req.promoted, replica=rep.replica_id,
                p_long=req.p_long, klass=req.klass,
                degraded=bool(req.meta.get("degraded"))))

    def _drain_real(self, rep, eng: RealEngine, max_new_tokens: int) -> None:
        """Serial wall-clock loop: pop -> tokenize -> fused decode.

        Under a preemptive policy, a queued request whose key strictly
        beats the running one (or, for MLFQ, a running request that
        exhausts its quantum) stops the fused loop at the next segment
        boundary (§3.4 cancellation); the evicted request re-enters the
        queue with its policy requeue key and the tokens generated so
        far, and later resumes by re-prefilling prompt + generated prefix
        (cheap re-prefill: greedy decode makes the resumed sequence
        bitwise-identical to an uninterrupted one).
        """
        import time as _time
        from repro.core.policy import MODE_SRPT
        if self._tokenizer is None:
            self._tokenizer = HashTokenizer(eng.cfg.vocab_size)
        pol = self.policy_obj
        obs = self.obs
        rec = obs.recorder if obs is not None else None
        trk = f"replica{rep.replica_id}"
        t = eng.busy_until
        while True:
            if pol.preemptive:
                req, t = self._pop_arrival_aware(rep, t)
            else:
                req = rep.queue.pop(now=t)
            if req is None:
                break
            t = max(t, req.arrival)
            if self._maybe_shed(rep, req, t):
                continue
            ids, n_total, resume = self._prepare_ids(req, eng,
                                                     max_new_tokens)
            n_new = max(1, n_total - len(resume))
            used = req.meta.get("used_s", 0.0)
            key0 = req.meta.get("policy_key0", 0.0)
            level = req.meta.get("mlfq_level", 0)
            evict_reason = []
            cancel_cb = None
            if pol.preemptive:
                wall0 = _time.monotonic()
                # SRPT decays from the ADMISSION key by total service
                # received; level policies carry their current queue key.
                # used/elapsed are wall seconds against model-calibrated
                # keys — an approximation unless the policy's short/long
                # moments are calibrated to this engine.
                base_key = key0 if pol.mode == MODE_SRPT \
                    else req.meta.get("queue_key", key0)

                def cancel_cb():
                    elapsed = _time.monotonic() - wall0
                    best = self._best_eligible(rep, t + elapsed)
                    if best is None:
                        return False
                    quantum = pol.quantum(req.p_long)
                    if (quantum is not None and level == 0
                            and used + elapsed > quantum):
                        evict_reason.append("quantum")
                        return True
                    run_key = pol.running_key(base_key, used + elapsed)
                    if pol.should_preempt(run_key, best[0]):
                        evict_reason.append("preempt")
                        return True
                    return False

            deadline_hit = []
            dl = self._deadline_of(req) \
                if self.deadline_mode == "sojourn" else None
            if dl is not None:
                wall_dl0 = _time.monotonic()
                waited = max(0.0, t - req.arrival)
                inner_cb = cancel_cb

                def cancel_cb(_inner=inner_cb, _w0=wall_dl0, _dl=dl,
                              _waited=waited):
                    # sojourn budget: queue wait already spent + wall time
                    # in this attempt; expiry stops the fused loop at the
                    # next segment boundary -> terminal ``timeout``
                    if _waited + (_time.monotonic() - _w0) > _dl:
                        deadline_hit.append(True)
                        return True
                    return _inner() if _inner is not None else False

            if req.start is None:
                req.start = t                 # first dispatch
                if rec is not None:
                    rec.span("queue_wait", req.req_id, req.arrival, t,
                             track=f"req{req.req_id}")
            # injected transient backend error at dispatch time
            if self.faults is not None:
                spec = self.faults.transient_due(rep.replica_id, t)
                if spec is not None:
                    t = self._retry_or_fail(rep, req, t,
                                            TransientBackendError(
                                                "injected transient "
                                                "backend error"))
                    continue
            self._decoding[rep.replica_id] = req.req_id
            wall_gen0 = _time.monotonic()
            if rec is not None:
                # the engine times its own prefill/decode regions, on
                # the drain's clock from this dispatch's instant on
                clock0, rec.clock = rec.clock, anchored_clock(t)
            try:
                out = eng.generate(ids, max_new_tokens=n_new,
                                   cancel_cb=cancel_cb, req_id=req.req_id)
            except Exception as e:
                # engine crash mid-generation (injected at a segment
                # boundary, or organic): the popped request must not be
                # lost — charge the wall time burned, then requeue or
                # fail through the shared epilogue.  Tokens decoded by
                # the dead engine are gone (no resume credit).
                elapsed = _time.monotonic() - wall_gen0
                t += elapsed
                if isinstance(e, EngineCrash):
                    t += e.repair_s           # replica down for repair
                eng.busy_until = t
                t = self._retry_or_fail(rep, req, t, e)
                continue
            finally:
                self._decoding.pop(rep.replica_id, None)
                if rec is not None:
                    rec.clock = clock0
            service = out["service_s"]
            tokens = list(resume) + list(out["tokens"])
            req.meta.setdefault("ttft_s", out["ttft_s"])
            t += service
            eng.busy_until = t
            if out.get("cancelled"):
                if req.req_id in self._disconnected:
                    self._disconnected.discard(req.req_id)
                    req.finish = t
                    self._finish(CompletionResponse(
                        request_id=req.req_id, text="",
                        tokens_generated=len(tokens),
                        queue_wait_s=req.start - req.arrival,
                        service_s=used + service,
                        ttft_s=req.start - req.arrival + req.meta["ttft_s"],
                        promoted=req.promoted, replica=rep.replica_id,
                        p_long=req.p_long, klass=req.klass,
                        status="cancelled",
                        error="client disconnect (mid-generation)",
                        degraded=bool(req.meta.get("degraded"))))
                    continue                  # client disconnect: drained
                if deadline_hit:
                    self.fault_stats["timeouts"] += 1
                    self.router.release(rep.replica_id, req)
                    req.finish = t
                    self._finish(CompletionResponse(
                        request_id=req.req_id, text="",
                        tokens_generated=len(tokens),
                        queue_wait_s=req.start - req.arrival,
                        service_s=used + service,
                        ttft_s=req.start - req.arrival + req.meta["ttft_s"],
                        promoted=req.promoted, replica=rep.replica_id,
                        p_long=req.p_long, klass=req.klass,
                        status="timeout",
                        error="deadline expired in service",
                        retries=req.meta.get("fault_retries", 0),
                        degraded=bool(req.meta.get("degraded"))))
                    continue                  # in-service deadline expiry
                if len(tokens) >= n_total:
                    pass                      # done at the boundary anyway
                else:
                    # preemption / demotion: re-enqueue the remaining work
                    self._requeue_evicted(rep, req, tokens, used + service,
                                          key0, level, evict_reason)
                    continue
            total_service = used + service
            req.finish = t
            self.router.on_dispatch(rep.replica_id, req, t,
                                    service_estimate=total_service)
            self.router.record_success(rep.replica_id, t)
            self._finish(CompletionResponse(
                request_id=req.req_id, text=tokens_to_text(tokens),
                tokens_generated=len(tokens),
                queue_wait_s=req.start - req.arrival,
                service_s=total_service,
                ttft_s=req.start - req.arrival + req.meta["ttft_s"],
                promoted=req.promoted, replica=rep.replica_id,
                p_long=req.p_long, klass=req.klass,
                retries=req.meta.get("fault_retries", 0),
                degraded=bool(req.meta.get("degraded")),
                accept_rate=out.get("accept_rate")))

    def _drain_batched(self, rep, eng: BatchedRealEngine,
                       max_new_tokens: int) -> None:
        """Micro-batched wall-clock drain: up to ``eng.n_lanes`` requests
        decode concurrently under the engine's KV budget.

        The queue stays the single source of dispatch order: the engine's
        lane back-fill pulls through :meth:`SJFQueue.pop_many`, so the
        starvation guard is re-evaluated between every pop (a promoted
        waiter takes the next vacant lane even when its key sorts last).
        Admission is memory-aware — a head whose worst-case KV footprint
        does not fit the budget blocks back-fill until lanes retire.
        Client disconnects evict the lane at the next segment boundary
        (per-lane §3.4 semantics).  Preemptive policies use the serial
        ``_drain_real`` path (lane eviction by key is future work); the
        server routes them there before calling this.
        """
        import time as _time
        if self._tokenizer is None:
            self._tokenizer = HashTokenizer(eng.cfg.vocab_size)
        obs = self.obs
        rec = obs.recorder if obs is not None else None
        eng.recorder = rec                     # lane spans (engine.py)
        t_base = eng.busy_until
        wall0 = _time.monotonic()

        def now() -> float:
            return t_base + (_time.monotonic() - wall0)

        def source(k: int):
            items = []
            while len(items) < k:
                got = rep.queue.pop_many(k - len(items), now=now())
                if not got:
                    break
                for req in got:
                    if self._maybe_shed(rep, req, now()):
                        continue              # shed: pull a replacement
                    if rec is not None:
                        rec.span("queue_wait", req.req_id, req.arrival,
                                 now(), track=f"req{req.req_id}")
                    ids, n_total, resume = self._prepare_ids(
                        req, eng, max_new_tokens)
                    items.append({"req_id": req.req_id, "ids": ids,
                                  "max_new": max(1, n_total - len(resume)),
                                  "tenant": req.tenant,
                                  "meta": {"req": req,
                                           "resume": list(resume)}})
            return items

        def cancel_check(state) -> bool:
            if state.req_id in self._disconnected:
                return True
            if self.deadline_mode == "sojourn":
                req = state.meta["req"]
                dl = self._deadline_of(req)
                if dl is not None and (now() - req.arrival) > dl:
                    state.meta["deadline_hit"] = True
                    return True
            return False

        def requeue_or_fail(req, now_t) -> None:
            """Crashed-lane victim: bounded retry with the original
            arrival (and a resume prefix — re-prefill is work-conserving)
            or a terminal ``failed`` response."""
            self._retry_or_fail(rep, req, now_t, EngineCrash(
                "injected lane crash"), charge_backoff=False)

        def on_finish(state, out):
            req = state.meta["req"]
            tokens = state.meta["resume"] + out["tokens"]
            if req.start is None:
                req.start = max(out["admit_t"], req.arrival)
            if out.get("crashed"):
                # lane died at a segment boundary: keep the decoded prefix
                # for the resume re-prefill, then retry or fail
                req.meta["resume_tokens"] = tokens
                requeue_or_fail(req, out["finish_t"])
                return
            if out["cancelled"]:
                # disconnect wins over a deadline that fired the same
                # segment (the client is gone either way)
                timed_out = state.meta.get("deadline_hit") \
                    and req.req_id not in self._disconnected
                if timed_out:
                    self.fault_stats["timeouts"] += 1
                    self.router.release(rep.replica_id, req)
                else:
                    self._disconnected.discard(req.req_id)
                req.finish = max(out["finish_t"], req.start)
                self._finish(CompletionResponse(
                    request_id=req.req_id, text="",
                    tokens_generated=len(tokens),
                    queue_wait_s=req.start - req.arrival,
                    service_s=req.finish - req.start,
                    ttft_s=out["ttft_s"], promoted=req.promoted,
                    replica=rep.replica_id, p_long=req.p_long,
                    klass=req.klass,
                    status="timeout" if timed_out else "cancelled",
                    error="deadline expired in service" if timed_out
                    else "client disconnect (mid-generation)",
                    retries=req.meta.get("fault_retries", 0),
                    degraded=bool(req.meta.get("degraded"))))
                return
            req.finish = max(out["finish_t"], req.start)
            req.meta.setdefault("ttft_s", out["ttft_s"])
            self.router.on_dispatch(rep.replica_id, req, req.finish,
                                    service_estimate=out["service_s"])
            self.router.record_success(rep.replica_id, req.finish)
            self._finish(CompletionResponse(
                request_id=req.req_id, text=tokens_to_text(tokens),
                tokens_generated=len(tokens),
                queue_wait_s=req.start - req.arrival,
                service_s=req.finish - req.start,
                ttft_s=req.start - req.arrival + req.meta["ttft_s"],
                promoted=req.promoted, replica=rep.replica_id,
                p_long=req.p_long, klass=req.klass,
                retries=req.meta.get("fault_retries", 0),
                degraded=bool(req.meta.get("degraded")),
                accept_rate=out.get("accept_rate")))

        # exception-safe lane driving: a whole-engine crash raised from a
        # segment boundary evicts every busy lane back into the queue
        # (bounded per-request retries), and crash/requeue churn re-enters
        # run_lanes until the queue truly drains.  The pass cap is a
        # safety net — fault plans are finite, so it is never hit unless
        # an engine raises unboundedly, in which case remaining requests
        # terminate as failed instead of looping forever.
        for _pass in range(64):
            try:
                eng.run_lanes(source, on_finish, cancel_check=cancel_check,
                              now_fn=now)
            except Exception as e:
                t_err = now()
                mgr = eng.lane_manager
                if mgr is not None:
                    for lane in list(mgr.busy_lanes()):
                        st = mgr.evict(lane)
                        victim = st.meta["req"]
                        victim.meta["resume_tokens"] = \
                            st.meta["resume"] + list(st.tokens)
                        if victim.start is None:
                            victim.start = max(st.admit_t, victim.arrival)
                        self._retry_or_fail(rep, victim, t_err, e,
                                            charge_backoff=False)
                # items popped from the queue but not yet admitted to a
                # lane would otherwise vanish with the engine's stack
                for item in eng.take_pending():
                    pend = item["meta"]["req"]
                    self._retry_or_fail(rep, pend, t_err, e,
                                        charge_backoff=False)
            if not rep.queue.live():
                break
        else:
            for req in list(rep.queue.live()):
                rep.queue.remove(req.req_id)
                req.finish = now()
                self._finish(CompletionResponse(
                    request_id=req.req_id, text="", tokens_generated=0,
                    queue_wait_s=max(0.0, now() - req.arrival),
                    service_s=0.0, replica=rep.replica_id,
                    p_long=req.p_long, klass=req.klass, status="failed",
                    error="engine unable to drain (retry passes exhausted)",
                    retries=req.meta.get("fault_retries", 0)))
        eng.busy_until = now()

    def _prepare_ids(self, req, eng, max_new_tokens: int):
        """Token budget + input ids for one dispatch, shared by the serial
        and batched drains (their truncation must match exactly — the
        batched engine's bitwise-equivalence contract compares against
        serial runs of the same inputs).  Returns (ids, n_total, resume):
        the prompt is clamped so prompt + n_total fits ``eng.max_len``,
        and a preempted request's generated prefix is re-prefilled after
        the prompt (the PR-4 resume rule)."""
        n_total = max(1, min(max_new_tokens, req.meta["output_tokens"]))
        resume = req.meta.get("resume_tokens", [])
        prompt_ids = self._tokenizer.encode(req.prompt)[: max(
            1, eng.max_len - n_total)]
        ids = np.concatenate([np.asarray(prompt_ids, np.int64),
                              np.asarray(resume, np.int64)]) \
            if resume else prompt_ids
        return ids, n_total, resume

    def _pop_arrival_aware(self, rep, t: float):
        """Dispatch decision for preemptive real drains: only requests that
        have (virtually) arrived by ``t`` compete — otherwise the best key
        would always dispatch first and nothing could ever preempt.  Jumps
        the clock to the next arrival when the queue is momentarily empty.
        Applies the starvation guard, then the policy key.  One unsorted
        O(n) scan per dispatch."""
        live = rep.queue.live()
        if not live:
            return None, t
        if all(r.arrival > t for r in live):
            t = min(r.arrival for r in live)
        oldest = best = None
        for r in live:
            if r.arrival > t:
                continue
            if oldest is None or (r.arrival, r.req_id) \
                    < (oldest.arrival, oldest.req_id):
                oldest = r
            if best is None or (r.meta["queue_key"], r.req_id) \
                    < (best.meta["queue_key"], best.req_id):
                best = r
        tau = rep.queue.tau
        if tau is not None and (t - oldest.arrival) > tau:
            req = oldest
            req.promoted = True
            rep.queue.stats["promotions"] += 1
        else:
            req = best
        rep.queue.remove(req.req_id)
        rep.queue.stats["dispatched"] += 1
        rep.queue.policy_obj.note_dispatch(req.meta.get("queue_key", 0.0))
        return req, t

    def _best_eligible(self, rep, now: float):
        """Best (key, Request) among queued requests arrived by ``now``.
        Fast path: the heap head is the global best — if it has arrived,
        it is the answer in O(1); otherwise fall back to one unsorted
        scan (polled every fused-decode segment, so no sorting here)."""
        top = rep.queue.peek()
        if top is not None and top[1].arrival <= now:
            return top
        best = None
        for r in rep.queue.live():
            if r.arrival <= now:
                k = r.meta["queue_key"]
                if best is None or k < best[0]:
                    best = (k, r)
        return best

    def _requeue_evicted(self, rep, req, tokens, used_s, key0, level,
                         evict_reason) -> None:
        """Re-enqueue a preempted/demoted request with its resume state,
        using the policy's requeue hooks (custom Policy subclasses can
        override them)."""
        from repro.core.policy import MODE_SRPT
        pol = self.policy_obj
        req.meta["resume_tokens"] = tokens
        req.meta["used_s"] = used_s
        cur_key = req.meta.get("queue_key", key0)
        if evict_reason and evict_reason[0] == "quantum":
            req.meta["mlfq_level"] = level + 1
            new_key = pol.requeue_key(cur_key, used_s)     # demotion
        else:
            base = key0 if pol.mode == MODE_SRPT else cur_key
            new_key = pol.running_key(base, used_s)        # plain eviction
        rep.queue.push_requeue(req, new_key)

    # ---------------------------------------------------------------- stats
    def percentile(self, q: float, klass: Optional[str] = None,
                   attr: str = "sojourn_s",
                   statuses: Sequence[str] = ("ok",)) -> float:
        """Latency percentile over terminal responses.  By default only
        ``ok`` responses count (shed/failed/cancelled requests have no
        meaningful sojourn); pass ``statuses=None`` to pool everything."""
        vals = [getattr(r, attr) for r in self.responses
                if (klass is None or self._klass_of(r) == klass)
                and (statuses is None or r.status in statuses)]
        return float(np.percentile(vals, q)) if vals else float("nan")

    @property
    def ok_responses(self) -> List[CompletionResponse]:
        return [r for r in self.responses if r.status == "ok"]

    def _klass_of(self, resp: CompletionResponse) -> str:
        if resp.klass:
            return resp.klass
        toks = resp.tokens_generated
        return "short" if toks < 200 else ("medium" if toks < 800 else "long")

    @property
    def promotions(self) -> int:
        return sum(rep.queue.stats["promotions"]
                   for rep in self.router.replicas)
