"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``chipbench/configs/<config>.json``) and a traffic mix
(``chipbench/traffic/<traffic>.json``).  One process holds the chip and
runs the paper's deployment: an open-loop client, the HTTP/SSE sidecar on
loopback, predictive admission, the SJF queue, ``InProcessBackend`` and
``RealEngine`` (bucketed prefill, fused decode segments) with the model at
its published widths in bfloat16.

1. Device check: JAX must find a TPU and as many chips as the cell asks
   for, else the run says why and exits 2 before building anything.
2. Set-up (``setup_s``, from process start to the first due request):
   weights made on the device from the seed, the predictor trained on the
   mix's profile, the stack built, and one request through the served path
   for each prefill bucket the run's prompts use (which also compiles or
   loads the decode segment).  JAX's compilation cache lives in
   ``.chipbench_cache/jax`` inside the checkout, and caches every program.
3. The window: requests go out at their due times for ``--seconds``;
   those sent are awaited up to the mix's ``drain_cap_s`` after it.
   ``--trace 1`` also records a profiler trace of a few seconds of it.
4. ``memory_peak_bytes`` is read, the program's state freed, and then the
   checks that decide ``correct`` run (``checks.py``).

The last line of standard output is the result: with ``--trace 0`` the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics (each
read by ``chipbench/metrics/<name>.py``) and the trace's breakdown.  The
numbers compared are the last lines of standard error and the result's
last key, ``checks``.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")
CACHE_DIR = os.path.join(ROOT, ".chipbench_cache", "jax")
TRACE_DIR = os.path.join(ROOT, ".chipbench_cache", "trace")
HOST = "127.0.0.1"
LEAD_S = 0.5             # the first due time after set-up ends

#: the traced stretch of the window: it starts ``TRACE_AT`` of the way in
#: and lasts ``TRACE_S`` seconds, or the rest of the window if shorter
TRACE_AT = 0.3
TRACE_S = 3.0


def say(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


class NoChip(SystemExit):
    pass


def device_check(chips: int) -> list:
    """The chips to use; exits 2 with the reason when there is no TPU or
    too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        say(f"no TPU: JAX's devices are {devs[0].platform} "
            f"({devs[0].device_kind}); this benchmark runs on the chip only")
        raise NoChip(2)
    if len(devs) < chips:
        say(f"the cell needs {chips} chips, JAX finds {len(devs)}")
        raise NoChip(2)
    return devs


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> tuple:
    """(BENCHMARK.json, the cell, its configuration, its traffic mix)."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    c = load_json(os.path.join(ROOT, conf["file"]))
    mix = load_json(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    return bench, cell, c, mix


class CompileMeter:
    """JAX's compile events (a persistent-cache hit is counted as a compile
    that loaded its executable), and cache hits and misses."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.compiles, self.seconds, self.hits, self.misses = 0, 0.0, 0, 0

    def install(self) -> "CompileMeter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == self.COMPILE:
            self.compiles += 1
            self.seconds += secs

    def _event(self, event: str, **kw) -> None:
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.seconds,
                "hits": self.hits, "misses": self.misses}


@dataclass
class RunData:
    """What a per-layer metric reader is given."""
    config: dict
    records: list            # client.Record of the window's requests
    responses: dict          # request id -> server CompletionResponse
    spans: list              # FlightRecorder spans of the window
    ranking: object          # RankingMonitor over the window's requests
    trace: object            # trace_reduce.Trace, or None
    reduced: Optional[dict]  # trace_reduce.reduce(trace), or None
    peak: dict               # roofline.peaks of the device


def pick_warmup(sched, buckets) -> list:
    """One request of the schedule for each prefill bucket it uses."""
    from chipbench.traffic_gen import prompt_tokens
    from repro.serving.generate import bucket_for
    seen = {}
    for r in sched:
        seen.setdefault(bucket_for(prompt_tokens(r.prompt), buckets), r)
    return [seen[b] for b in sorted(seen)]


def records_for(reqs, t0: float) -> list:
    from chipbench.client import Record
    return [Record(i, r.klass, r.max_tokens, r.prompt, t0 + r.offset_s)
            for i, r in enumerate(reqs)]


class Tracer:
    """Takes a profiler trace from ``t_a`` to ``t_b`` (monotonic) on a
    thread of its own, with the harness's ``chipbench.window`` span over
    exactly the traced stretch."""

    def __init__(self, t_a: float, t_b: float):
        import threading
        self.t_a, self.t_b = t_a, t_b
        self.path = None
        self.error = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="chipbench-tracer")

    def start(self) -> None:
        self._thread.start()

    def join(self) -> None:
        self._thread.join()

    def _run(self) -> None:
        import jax
        try:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            time.sleep(max(0.0, self.t_a - time.monotonic()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("chipbench.window"):
                    time.sleep(max(0.0, self.t_b - time.monotonic()))
            finally:
                jax.profiler.stop_trace()
            self.path = glob.glob(os.path.join(
                TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb"))[0]
        except Exception as e:           # reported; the reader finds none
            self.error = f"{type(e).__name__}: {e}"


async def serve_window(sidecar, warm: list, window: list, deadline: float,
                       tracer: Optional[Tracer], on_warm):
    """Warm the served path, then run the open-loop window."""
    from chipbench.client import OpenLoop
    await sidecar.start()
    try:
        warm_client = OpenLoop(HOST, sidecar.port, warm,
                               time.monotonic() + 900.0)
        warm_client.start()
        await asyncio.to_thread(warm_client.join, 960.0)
        t0 = on_warm()
        for r in window:
            r.due += t0
        client = OpenLoop(HOST, sidecar.port, window, deadline + t0)
        if tracer is not None:
            tracer.t_a += t0
            tracer.t_b += t0
            tracer.start()
        client.start()
        await asyncio.to_thread(client.join, deadline + 120.0)
        if tracer is not None:
            await asyncio.to_thread(tracer.join)
        return client
    finally:
        await sidecar.shutdown(drain_s=0.0)


def run_cell(cell: dict, c: dict, mix: dict, metrics: list, seed: int,
             seconds: float, trace: bool, require_tpu: bool = True,
             control: bool = False, limit: Optional[float] = None,
             fault=None) -> dict:
    """One run of a cell.  Returns the result dict (the last key is
    ``checks``).  ``metrics`` lists the entries of ``BENCHMARK.json`` that
    this run reports: the cell's end-to-end metrics, or with ``trace`` its
    per-layer metrics.  ``control`` puts the float8 control in the
    program's place in the logit-gap comparison, on the same sample of
    served requests; ``fault(engine)`` plants a fault in the served path
    (tests)."""
    import jax

    from chipbench import checks, reference, roofline, stack, stats
    from chipbench import trace_reduce, traffic_gen
    from chipbench.traffic_gen import prompt_tokens

    devs = device_check(cell["chips"]) if require_tpu else jax.devices()
    meter = CompileMeter().install()
    phases = {"jax_init_s": time.monotonic() - T_START}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"device {device}")
    peak = roofline.peaks(device["kind"]) if require_tpu else None

    t = time.monotonic()
    cfg = stack.arch_config(c)
    params = stack.make_params(c, cfg, seed)
    phases["weights_s"] = time.monotonic() - t
    t = time.monotonic()
    predictor = stack.build_predictor(c["predictor_profile"])
    phases["predictor_s"] = time.monotonic() - t

    sched = traffic_gen.schedule(mix, seconds)
    largest = max(prompt_tokens(r.prompt) for r in sched)
    t = time.monotonic()
    sidecar, probe, tau = stack.build(
        c, cfg, params, predictor, seed,
        max_new_tokens=c["max_len"] - largest, tracing=trace)
    engine = sidecar.backends[0].engine
    if fault is not None:
        fault(engine)
    phases["build_s"] = time.monotonic() - t
    warm = records_for(pick_warmup(sched, engine.buckets), 0.0)
    for r in warm:
        r.max_tokens = min(r.max_tokens, 2 * c["segment_len"] + 1)
    window = records_for(sched, LEAD_S)
    tracer = None
    if trace:
        t_a = TRACE_AT * seconds
        tracer = Tracer(LEAD_S + t_a,
                        LEAD_S + t_a + min(TRACE_S, seconds - t_a))
    marks = {}

    def on_warm() -> float:
        from repro.serving.observability import RankingMonitor
        obs = sidecar.obs
        if obs.recorder is not None:
            obs.recorder.clear()
        obs.ranking = RankingMonitor(window=max(512, len(window)))
        probe.pops.clear()
        marks["responses"] = len(sidecar.server.responses)
        marks["meter"] = meter.snapshot()
        now = time.monotonic()
        marks["t0"] = now
        phases["warmup_s"] = now - t_warm
        marks["setup_s"] = now + LEAD_S - T_START
        return now

    t_warm = time.monotonic()
    client = asyncio.run(serve_window(
        sidecar, warm, window, seconds + mix["drain_cap_s"] + LEAD_S,
        tracer, on_warm))
    m0, m1 = marks["meter"], meter.snapshot()
    in_window = m1["compiles"] - m0["compiles"]
    say(f"setup_s {marks['setup_s']:.3f}: " + ", ".join(
        f"{k} {v:.3f}" for k, v in phases.items())
        + f"; compile events in set-up {m0['compiles']} "
        f"({m0['compile_s']:.3f} s; cache hits {m0['hits']}, "
        f"misses {m0['misses']}); tau {tau:.4f} s")
    say(f"compile events inside the window: {in_window} "
        f"(cache hits {m1['hits'] - m0['hits']}, "
        f"misses {m1['misses'] - m0['misses']})")
    warm_bad = [r for r in warm if not r.ok]
    if warm_bad:
        say(f"warm-up failed: {[(r.status, r.error) for r in warm_bad]}")
    late = sorted(client.lateness) or [0.0]
    recs = client.records
    n_ok = sum(r.ok for r in recs)
    say(f"requests sent {len(recs)}, succeeded {n_ok}, failed "
        f"{len(recs) - n_ok} (still open at the drain cap "
        f"{client.unfinished}); generator late by median "
        f"{stats.median(late) * 1e3:.3f} ms, max {late[-1] * 1e3:.3f} ms")
    srv = sidecar.server
    responses = {r.request_id: r for r in srv.responses[marks["responses"]:]}
    close = marks["t0"] + LEAD_S + seconds
    backlog = sum(1 for r in recs if r.t_first is None or r.t_first > close)
    busy = sum(r.service_s for r in responses.values() if r.ok)
    say(f"engine busy {busy:.3f} s, {100 * busy / seconds:.1f}% of the "
        f"window; requests first answered after the window closed: "
        f"{backlog}")
    say(f"promotions by the starvation guard: "
        f"{sum(p['promoted'] for p in probe.pops)} of {len(probe.pops)} "
        f"dispatches; output lengths cut to the cap: "
        f"{sum(r.cut for r in sched)} of {len(sched)}")
    stats_ = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats_.get("peak_bytes_in_use", 0))

    rd = None
    if trace:
        spans = [] if srv.obs.recorder is None else srv.obs.recorder.spans()
        tr = reduced = None
        if tracer.path is not None:
            tr = trace_reduce.read(tracer.path)
            reduced = trace_reduce.reduce(tr)
        else:
            say(f"no trace: {tracer.error}")
        rd = RunData(c, recs, responses, spans, srv.obs.ranking, tr, reduced,
                     peak)

    # free the program's state before the reference runs
    del sidecar, srv, engine, params
    gc.collect()

    bad, wrong = checks.wire_faults(recs, c["vocab_size"])
    order = checks.dispatch_faults(probe.pops, c["policy"])
    picked = checks.sample(recs, seed, c["vocab_size"])
    t = time.monotonic()
    seqs = [(reference.tokenize(r.prompt, c["vocab_size"]),
             checks.served_ids(r, c["vocab_size"])) for r in picked]
    gaps, ctrl = [], []
    if seqs:
        got = reference.served_gaps(c, seed, seqs, control=control)
        gaps, ctrl = got if control else (got, None)
    gap = max((float(g.max()) for g in gaps), default=None)
    say(f"reference over {len(seqs)} requests, "
        f"{sum(len(s) for _, s in seqs)} served tokens, in "
        f"{time.monotonic() - t:.3f} s")
    if control:
        # the control takes the program's place in the comparison
        say(f"control run: the program's own logit_gap is {gap}")
        program_gap = gap
        gap = max((float(g.max()) for g in ctrl), default=None)
    limit = c["logit_gap_limit"] if limit is None else limit
    check = {
        "answers_not_clean": {"value": bad, "limit": 0},
        "answers_wrong_length": {"value": wrong, "limit": 0},
        "dispatch_order_faults": {"value": order, "limit": 0},
        "logit_gap": {"value": gap, "limit": limit},
    }
    correct = (bad == 0 and wrong == 0 and order == 0 and gap is not None
               and gap <= limit)

    if trace:
        values = {}
        for m in metrics:
            reader = importlib.import_module(f"chipbench.metrics.{m['name']}")
            v = reader.read(rd)
            if v is not None:
                values[m["name"]] = v
    else:
        shorts = [r for r in recs if r.klass == "short"]
        tpots = [v for v in map(stats.tpot_ms, recs) if v is not None]
        values = {
            "ttft_short_p50_s": stats.percentile(
                map(stats.ttft_s, shorts), 50),
            "ttft_short_p90_s": stats.percentile(
                map(stats.ttft_s, shorts), 90),
            "ttft_p90_s": stats.percentile(map(stats.ttft_s, recs), 90),
            "tpot_p90_ms": stats.percentile(tpots, 90),
            "setup_s": marks["setup_s"],
        }
    out = {"correct": correct, "attempted": len(recs),
           "failed": len(recs) - n_ok,
           "metrics": {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in metrics if m["name"] in values},
           "device": device}
    out["diagnostics"] = {"program_logit_gap": program_gap} if control else {}
    out["diagnostics"] |= {
        "engine_busy_share": busy / seconds, "answered_after_close": backlog,
        "promotions": sum(p["promoted"] for p in probe.pops),
        "late_ms_p50": stats.median(late) * 1e3, "late_ms_max": late[-1] * 1e3,
        "compiles_in_window": in_window, "setup_phases_s": phases}
    if trace and rd.reduced is not None:
        device["busy_s"] = rd.reduced["busy_s"]
        device["window_s"] = rd.reduced["window_s"]
        out["breakdown"] = {"device_ops": rd.reduced["device_ops"],
                            "idle_gaps": rd.reduced["idle_gaps"]}
        say(f"longest idle gaps: {rd.reduced['longest_gaps']}")
    out["checks"] = check
    return out


def cell_metrics(bench: dict, name: str, kind: str) -> list:
    """The entries of ``bench[kind]`` that cell ``name`` reports."""
    return [m for m in bench[kind]
            if name in m.get("workloads", [name])]


def print_result(out: dict) -> None:
    for k, v in out["checks"].items():
        say(f"check {k}: {v['value']} (limit {v['limit']})")
    print(json.dumps(out), flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, holding every program however fast it compiles."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    bench, cell, c, mix = load_cell(args.workload)
    setup_cache()
    kind = "per_layer" if args.trace else "end_to_end"
    try:
        out = run_cell(cell, c, mix, cell_metrics(bench, cell["name"], kind),
                       args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        return int(e.code)
    print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
