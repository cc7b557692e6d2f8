"""The served stack of one cell, built from the program's constructors.

It is wired as ``repro.launch.sidecar.build_sidecar`` wires ``--backend
real``: a ``ServiceTimeModel`` of the architecture, the predictor trained
on the mix's profile, tau calibrated from the model's short and long
service, one ``InProcessBackend`` over a ``RealEngine``, a
``ClairvoyantServer`` with sojourn deadlines and a ``Sidecar`` on
loopback.  Two things differ: the architecture comes from a configuration
file (``build_sidecar`` takes only a registered name), and the weights are
the benchmark's own, made from the seed (``weights.py``).

The harness puts its own spans around the program's layers here, without
changing them: ``chipbench.admission`` around the predictor call,
``chipbench.generate`` around each engine call, ``chipbench.prefill`` and
``chipbench.segment`` around the dispatch of each prefill and decode
segment (with the prompt length and the decode steps the segment runs,
which the roofline reader needs); these only in a traced run.  In every
run it logs each dispatch decision of the queue, which the dispatch-order
check reads.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass, field

import jax

from chipbench import weights


@dataclass
class Probe:
    """What the harness's wrappers saw."""
    pops: list = field(default_factory=list)    # dispatch decisions
    gen: dict = field(default_factory=dict)     # the request in service


def arch_config(c: dict):
    """The program's ``ArchConfig`` for configuration file ``c``, checked
    against the file's published sizes."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(c["arch"]), **c["overrides"])
    want = {"num_layers": c["num_hidden_layers"],
            "d_model": c["hidden_size"],
            "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_key_value_heads"],
            "head_dim": c["head_dim"], "d_ff": c["intermediate_size"],
            "vocab_size": c["vocab_size"],
            "tie_embeddings": c["tie_word_embeddings"],
            "rope_theta": c["rope_theta"], "norm_eps": c["rms_norm_eps"],
            "dtype": c["torch_dtype"], "block_pattern": ("attn",),
            "mlp_activation": "silu", "qk_norm": False}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"program config differs from the file: {bad}")
    return cfg


def make_params(c: dict, cfg, seed: int):
    """The served weights, made on the device in one jitted call, checked
    against the program's own parameter layout."""
    from repro.models.model import LM
    params = jax.jit(functools.partial(weights.program_params, c))(
        weights.seed_words(seed))
    want, _ = LM(cfg).abstract_params()
    if jax.tree.structure(want) != jax.tree.structure(params) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(params))):
        raise ValueError("benchmark weights do not match the program's "
                         "parameter layout")
    return jax.block_until_ready(params)


def build_predictor(profile: str):
    from repro.launch.serve import build_predictor as build
    return build(profile)


def build(c: dict, cfg, params, predictor, seed: int, max_new_tokens: int,
          tracing: bool):
    """The sidecar (not started) and the harness's probe."""
    from repro.core.calibration import calibrate_tau
    from repro.core.simulation import ServiceDist
    from repro.serving.backends import InProcessBackend
    from repro.serving.engine import RealEngine
    from repro.serving.faults import CircuitBreaker, RetryPolicy
    from repro.serving.http_sidecar import Sidecar
    from repro.serving.observability import Observability
    from repro.serving.server import ClairvoyantServer
    from repro.serving.service_time import ServiceTimeModel

    model = ServiceTimeModel.from_arch(cfg, chips=1)
    short = ServiceDist(model.service(64, 60), 0.3 * model.service(64, 60))
    long_ = ServiceDist(model.service(64, 1400),
                        0.3 * model.service(64, 1400))
    tau = calibrate_tau(short, long_, multiplier=c["tau_mult"])
    engine = RealEngine(cfg, params=params, seed=seed, max_len=c["max_len"],
                        segment_len=c["segment_len"])
    backend = InProcessBackend(engine)
    backend.replica_id = 0
    server = ClairvoyantServer(
        policy=c["policy"], tau=tau, predictor=predictor,
        service_model=model, engines=[backend], seed=seed, fault_plan=None,
        retry=RetryPolicy(seed=seed), deadline_s=None,
        deadline_mode="sojourn", max_queue_depth=None,
        breaker=CircuitBreaker(recovery_s=5.0))
    if tracing:
        server.attach_observability(Observability.default(tracing=True))
    sidecar = Sidecar(server, host="127.0.0.1", port=0, model=c["name"],
                      max_inflight=256, drain_s=30.0,
                      max_new_tokens=max_new_tokens)
    probe = Probe()
    _log_pops(server, probe)
    if tracing:
        _annotate(server, engine, probe, c)
    return sidecar, probe, tau


def _log_pops(server, probe: Probe) -> None:
    """Record each dispatch decision: the time, the tau in force, every
    waiting request's (id, arrival, P(Long)) and the one chosen."""
    queue = server.router.replicas[0].queue
    pop = queue.pop

    def logged_pop(now):
        waiting = [(r.req_id, r.arrival, r.p_long) for r in queue.live()]
        req = pop(now)
        if req is not None:
            probe.pops.append({"now": now, "tau": queue.tau,
                               "waiting": waiting, "chosen": req.req_id,
                               "promoted": bool(req.promoted)})
        return req

    queue.pop = logged_pop


def _annotate(server, engine, probe: Probe, c: dict) -> None:
    """Profiler spans around the calls into admission, the engine, and
    each prefill and decode-segment dispatch (traced runs only)."""
    ann = jax.profiler.TraceAnnotation
    predict = server._predict_probas

    def annotated_predict(*a, **kw):
        with ann("chipbench.admission"):
            return predict(*a, **kw)

    server._predict_probas = annotated_predict
    generate = engine.generate
    max_len, seg = c["max_len"], c["segment_len"]

    def annotated_generate(prompt_ids, max_new_tokens=32, **kw):
        plen = len(prompt_ids)
        probe.gen = {"plen": plen, "segment": 0,
                     "steps": min(max_new_tokens, max_len - plen) - 1}
        with ann("chipbench.generate", plen=plen, max_new=max_new_tokens):
            return generate(prompt_ids, max_new_tokens=max_new_tokens, **kw)

    engine.generate = annotated_generate
    prefill = engine._run_prefill

    def annotated_prefill(prompt_ids, *a, **kw):
        with ann("chipbench.prefill", tokens=len(prompt_ids)):
            return prefill(prompt_ids, *a, **kw)

    engine._run_prefill = annotated_prefill
    decoder = engine._decoder(seg)
    segment = decoder._segment

    def annotated_segment(*a, **kw):
        g = probe.gen
        first = g["segment"] * seg
        g["segment"] += 1
        with ann("chipbench.segment", plen=g["plen"], first_step=first,
                 steps=max(0, min(seg, g["steps"] - first))):
            return segment(*a, **kw)

    decoder._segment = annotated_segment
