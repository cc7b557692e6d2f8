"""Chip smoke run: the sidecar serves smollm-360m at its published widths on
one TPU, and the answers are checked.

    python chip_smoke.py

One process, no options, random weights from a fixed seed.  Phases:

1. Device check.  JAX must report a TPU; otherwise the script says why and
   exits nonzero before it builds anything.
2. Sidecar.  ``launch/sidecar.py`` builds the server through its own
   argument parser (``--backend real --arch smollm-360m --max-len 2048
   --policy sjf``) on a loopback port: predictive admission, the SJF queue,
   ``InProcessBackend``, ``RealEngine`` bucketed prefill and fused decode.
   A client on the same event loop sends four short and four long requests
   at once, two of them over SSE.  Checks: every answer is HTTP 200 with
   status ``ok`` and as many tokens as were asked; a streamed answer equals
   the plain answer to the same prompt; the longest answer equals
   ``RealEngine.generate_reference``.
3. Paged lanes.  An in-process ``ClairvoyantServer`` drain over one
   ``PagedBatchedEngine`` with the same weights (4 lanes, page 16), in two
   waves of four requests; one request of each wave shares a 256-token
   prefix.  Checks: no request is lost, the second wave hits the prefix
   cache, and every request's tokens equal the serial oracle's.

Token checks are bitwise up to the first differing token, which must fall
on a near-tie of the serial oracle: a top-2 logit gap below bf16
resolution, or below twice the logit noise measured, up to that step,
between the oracle's one-row programs and the same request in a batch of
``lanes`` rows (the paged phase).  bf16 programs of different shapes reduce in different
orders on the TPU; the report names each divergence and its gap.

The lines before the last are a smoke run's report, not a benchmark:
compile seconds, per-request TTFT, tokens and service seconds, peak device
memory.  The last line is ``{"ok": true, "device": {...}}``.  A failed
check raises and the script exits nonzero.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import math
import os
import sys
import time
import warnings
from dataclasses import dataclass

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: JAX warns with this when a donated buffer cannot be reused in place; on
#: the serving path that would mean a KV cache copied every segment.
DONATION_WARNING = "Some donated buffers were not usable"

#: The client gives up on the sidecar's answers after this long.
CLIENT_TIMEOUT_S = 600.0


@contextlib.contextmanager
def donation_fails():
    """Turn the donation warning into an error for the phases run inside."""
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message=DONATION_WARNING)
        yield


@dataclass(frozen=True)
class Plan:
    """Sizes of one smoke run.  Requests are ``(name, prompt_tokens,
    max_tokens, stream)``; requests sharing a name share a prompt."""
    arch: str
    max_len: int
    seed: int
    sidecar: tuple
    prefix: int
    page_size: int
    lanes: int
    waves: tuple            # paged phase; names starting "prefix" share it


CHIP_PLAN = Plan(
    arch="smollm-360m", max_len=2048, seed=0,
    # 4 short + 4 long; short-a and long-a go out twice, streamed and not.
    # Prompt lengths sit in two prefill buckets (64 and 512) to bound the
    # number of programs compiled.
    sidecar=(("short-a", 36, 32, True), ("short-a", 36, 32, False),
             ("short-b", 40, 32, False), ("short-c", 57, 32, False),
             ("long-a", 300, 256, True), ("long-a", 300, 256, False),
             ("long-b", 420, 512, False), ("long-c", 480, 384, False)),
    prefix=256, page_size=16, lanes=4,
    waves=((("prefix-a", 276, 48), ("p-short-a", 30, 32),
            ("p-short-b", 50, 32), ("p-long-a", 400, 128)),
           (("prefix-b", 296, 64), ("p-short-c", 20, 32),
            ("p-long-b", 350, 96), ("p-short-d", 60, 16))),
)


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


# ------------------------------------------------------------------ device
def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def versions() -> str:
    import importlib.metadata as md

    import jax
    import jaxlib
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    return f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, " \
           f"libtpu {libtpu}"


def peak_bytes() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else str(peak)


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


class CompileMeter:
    """Sums JAX's compile events (a persistent-cache hit is counted as a
    compile that loaded its executable)."""

    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0

    def install(self) -> "CompileMeter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event, duration, **_):
        if event == self.COMPILE:
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple:
        return (self.seconds, self.compiles, self.hits, self.misses)

    def since(self, snap: tuple) -> str:
        s, c, h, m = snap
        return (f"compile_s={self.seconds - s} compiles={self.compiles - c} "
                f"cache_hits={self.hits - h} cache_misses={self.misses - m}")


# ------------------------------------------------------------ token checks
def bf16_resolution(x: float) -> float:
    """Spacing of bfloat16 numbers (8 significant bits) at ``|x|``."""
    if x == 0.0:
        return 2.0 ** -133
    return 2.0 ** (math.floor(math.log2(abs(x))) - 7)


def first_divergence(got: list, want: list):
    """Index of the first step at which two token lists differ (the
    shorter length where one is a prefix of the other); None where they
    are equal."""
    if got == want:
        return None
    return next((i for i, (a, b) in enumerate(zip(got, want)) if a != b),
                min(len(got), len(want)))


def check_tokens(name: str, got: list, ref: dict, noise: float = 0.0) -> str:
    """Greedy tokens against the serial oracle (``generate_reference``).

    Equal tokens pass.  Otherwise the first differing step must be a
    near-tie of the oracle: its top-2 logit gap there is below the larger
    of bf16 resolution at the top logit and ``2 * noise``, where ``noise``
    bounds how far the compared program's logits stray from the oracle's
    by reduction order alone up to that step (two logits each off by at
    most ``noise`` can swap only if they are closer than ``2 * noise``).
    After that step the contexts differ and nothing more is compared.
    Returns a verdict; raises otherwise.
    """
    want = list(ref["tokens"])
    first = first_divergence(got, want)
    if first is None:
        return "bitwise equal to the serial oracle"
    if len(got) != len(want):
        raise AssertionError(f"{name}: {len(got)} tokens, oracle "
                             f"{len(want)}")
    top, second = ref["top2"][first]
    bound = max(bf16_resolution(top), 2 * noise)
    where = (f"first divergent step {first} of {len(want)}, oracle top-2 "
             f"gap there {top - second}, near-tie bound {bound} (bf16 "
             f"resolution {bf16_resolution(top)}, 2 x logit noise "
             f"{2 * noise})")
    if top - second >= bound:
        raise AssertionError(f"{name}: tokens differ from the serial oracle "
                             f"away from a near-tie: {where}")
    return f"equal up to a near-tie of the oracle; {where}"


def logit_noise(engine, ids, tokens, rows: int) -> float:
    """Largest |logit| difference between the oracle's one-row programs
    and the same request as row 0 of a ``rows``-row batch (every row a
    copy), teacher-forced along ``tokens``: the reduction-order noise
    between batch shapes, with no lane or page bookkeeping involved."""
    import jax.numpy as jnp
    import numpy as np
    one, c1, _ = engine._run_prefill(ids)
    many, cb, _ = engine._run_prefill_group([ids] * rows, pad_rows=rows)
    worst = 0.0
    for tok in [None] + list(tokens[:-1]):
        if tok is not None:
            one, c1 = engine._decode(engine.params, c1, {
                "tokens": jnp.full((1, 1), tok, jnp.int32)})
            many, cb = engine._decode(engine.params, cb, {
                "tokens": jnp.full((rows, 1), tok, jnp.int32)})
        worst = max(worst, float(np.max(np.abs(
            np.asarray(one)[0] - np.asarray(many)[0]))))
    return worst


def words(rng, n: int) -> list:
    return [f"w{x}" for x in rng.integers(0, 10 ** 6, n)]


def prompts_for(requests, seed: int, prefix: int = 0) -> dict:
    """One prompt per request name, ``prompt_tokens`` words long (the
    hash tokenizer makes one token per word); names starting "prefix"
    share their first ``prefix`` words."""
    import numpy as np
    rng = np.random.default_rng(seed)
    shared = words(rng, prefix)
    out = {}
    for name, n, *_ in requests:
        if name not in out:
            head = shared if name.startswith("prefix") else []
            out[name] = " ".join(head + words(rng, n - len(head)))
    return out


# ------------------------------------------------------------- the client
async def post(port: int, body: dict) -> dict:
    """POST one chat completion over loopback; returns the HTTP status,
    text, request id and terminal status, plus the client-side time to
    the first streamed delta."""
    t0 = time.monotonic()
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    payload = json.dumps(body).encode()
    writer.write((
        "POST /v1/chat/completions HTTP/1.1\r\nHost: smoke\r\n"
        "Content-Type: application/json\r\n"
        f"Content-Length: {len(payload)}\r\nConnection: close\r\n\r\n"
    ).encode() + payload)
    await writer.drain()
    raw, first_delta = b"", None
    while True:
        chunk = await reader.read(65536)
        if not chunk:
            break
        raw += chunk
        if body["stream"] and first_delta is None and b'"content"' in raw:
            first_delta = time.monotonic() - t0
    writer.close()
    try:
        await writer.wait_closed()
    except OSError:
        pass
    head, _, rest = raw.partition(b"\r\n\r\n")
    out = {"http": int(head.split()[1]), "client_s": time.monotonic() - t0,
           "client_ttft_s": first_delta}
    text = rest.decode()
    if not body["stream"]:
        doc = json.loads(text)
        out.update(text=doc["choices"][0]["message"]["content"],
                   status=doc.get("clairvoyant", {}).get("status"),
                   rid=int(doc["id"].split("-")[1]) if "id" in doc else None)
        return out
    frames = [f[len("data: "):] for f in text.split("\n\n")
              if f.startswith("data: ")]
    if not frames or frames[-1] != "[DONE]":
        raise AssertionError(f"SSE stream did not end in [DONE]: {text!r}")
    chunks = [json.loads(f) for f in frames[:-1]]
    finish = [c["choices"][0]["finish_reason"] for c in chunks
              if "choices" in c and c["choices"][0]["finish_reason"]]
    out.update(text="".join(c["choices"][0]["delta"].get("content", "")
                            for c in chunks if "choices" in c),
               status="ok" if finish == ["stop"] and all(
                   "error" not in c for c in chunks) else finish,
               rid=int(chunks[0]["id"].split("-")[1]))
    return out


def token_ids(text: str) -> list:
    """Invert ``serving.backends.tokens_to_text`` (``t<id>`` words)."""
    return [int(w[1:]) for w in text.split()]


# ------------------------------------------------------------ the phases
def sidecar_phase(plan: Plan):
    """Phase 2.  Returns (report, engine, predictor): the paged phase
    reuses the engine's config and weights and the trained predictor."""
    from repro.launch.sidecar import build_parser
    args = build_parser().parse_args([
        "--backend", "real", "--arch", plan.arch,
        "--max-len", str(plan.max_len), "--policy", "sjf",
        "--host", "127.0.0.1", "--port", "0", "--seed", str(plan.seed)])
    return asyncio.run(_sidecar_run(args, plan))


async def _sidecar_run(args, plan: Plan):
    import numpy as np
    from repro.launch.sidecar import build_sidecar
    from repro.serving.generate import bucket_for
    t0 = time.monotonic()
    sidecar = build_sidecar(args)
    backend = sidecar.backends[0]
    engine = backend.engine
    say(f"sidecar: built {engine.cfg.name} ({engine.cfg.num_layers} layers, "
        f"d_model {engine.cfg.d_model}, {engine.cfg.dtype}, max_len "
        f"{engine.max_len}) and the predictor in "
        f"{time.monotonic() - t0} s")
    prompts = prompts_for(plan.sidecar, plan.seed)
    lens = {name: len(backend.tokenizer.encode(p))
            for name, p in prompts.items()}
    for name, n, max_tokens, _ in plan.sidecar:
        if lens[name] != n or n + max_tokens >= plan.max_len:
            raise AssertionError(f"{name}: prompt {lens[name]} tokens "
                                 f"(planned {n}), +{max_tokens} new must "
                                 f"stay under max_len {plan.max_len}")
    buckets = sorted({bucket_for(n, engine.buckets) for n in lens.values()})
    for b in buckets:
        t1 = time.monotonic()
        engine.generate(np.ones(b, np.int32), max_new_tokens=2)
        say(f"sidecar: warm-up of prefill bucket {b} and the decode "
            f"segment took {time.monotonic() - t1} s")

    await sidecar.start()
    try:
        # output_tokens bounds the generation: without it the sidecar
        # caps it at a length sampled for the simulated backends
        bodies = [{"prompt": prompts[name], "max_tokens": m,
                   "output_tokens": m, "stream": stream}
                  for name, _, m, stream in plan.sidecar]
        answers = await asyncio.wait_for(
            asyncio.gather(*(post(sidecar.port, b) for b in bodies)),
            CLIENT_TIMEOUT_S)
    finally:
        await sidecar.shutdown(drain_s=5.0)
    served = {r.request_id: r for r in sidecar.server.responses}

    plain = {}
    for (name, n, m, stream), ans in zip(plan.sidecar, answers):
        resp = served.get(ans["rid"])
        say(f"sidecar: request {name} rid={ans['rid']} stream={stream} "
            f"prompt_tokens={n} asked={m} got={len(ans['text'].split())} "
            f"http={ans['http']} status={ans['status']} "
            f"client_ttft_s={ans['client_ttft_s']} "
            f"client_s={ans['client_s']} "
            f"ttft_s={getattr(resp, 'ttft_s', None)} "
            f"queue_s={getattr(resp, 'queue_wait_s', None)} "
            f"service_s={getattr(resp, 'service_s', None)}")
        if ans["http"] != 200 or ans["status"] != "ok":
            raise AssertionError(f"{name}: HTTP {ans['http']}, status "
                                 f"{ans['status']}")
        if len(ans["text"].split()) != m:
            raise AssertionError(f"{name}: asked {m} tokens, got "
                                 f"{len(ans['text'].split())}")
        if not stream:
            plain[name] = ans["text"]
    for (name, _, _, stream), ans in zip(plan.sidecar, answers):
        if stream and ans["text"] != plain[name]:
            raise AssertionError(f"{name}: streamed text differs from the "
                                 "plain answer to the same prompt")
    say("sidecar: every answer is HTTP 200/ok with the tokens asked; "
        "streamed text equals plain text")

    name, _, m, _ = max(plan.sidecar, key=lambda r: r[2])
    t1 = time.monotonic()
    ref = engine.generate_reference(backend.tokenizer.encode(prompts[name]),
                                    max_new_tokens=m)
    verdict = check_tokens(name, token_ids(plain[name]), ref)
    say(f"sidecar: {name} fused tokens vs generate_reference "
        f"({time.monotonic() - t1} s): {verdict}")
    return {"requests": len(answers)}, engine, sidecar.server.predictor


def paged_phase(plan: Plan, engine, predictor) -> dict:
    """Phase 3: in-process drain over paged lanes, checked per request
    against the serial oracle."""
    from repro.data.tokenizer import HashTokenizer
    from repro.serving.engine import PagedBatchedEngine
    from repro.serving.openai_api import CompletionRequest
    from repro.serving.server import ClairvoyantServer
    paged = PagedBatchedEngine(engine.cfg, params=engine.params,
                               max_len=plan.max_len, n_lanes=plan.lanes,
                               page_size=plan.page_size,
                               segment_len=engine.segment_len)
    server = ClairvoyantServer(policy="sjf", predictor=predictor,
                               engines=[paged], seed=plan.seed)
    flat = [r for wave in plan.waves for r in wave]
    prompts = prompts_for(flat, plan.seed + 1, plan.prefix)
    for name, n, m in flat:
        if n + m >= plan.max_len:
            raise AssertionError(f"{name}: {n} + {m} tokens must stay "
                                 f"under max_len {plan.max_len}")
    names = {}
    for i, wave in enumerate(plan.waves):
        reqs = [CompletionRequest(prompt=prompts[name])
                for name, _, _ in wave]
        # stamped on the engine's clock, so queue_s counts from this
        # wave's submission and not from the first wave's
        server.submit_many(reqs, arrivals=[paged.busy_until] * len(reqs),
                           true_output_tokens=[m for _, _, m in wave])
        names.update({r.request_id: (name, m)
                      for r, (name, _, m) in zip(reqs, wave)})
        t0 = time.monotonic()
        server.drain(max_new_tokens=plan.max_len)
        say(f"paged: wave {i + 1} drained in {time.monotonic() - t0} s")
    resps = {r.request_id: r for r in server.responses}
    if sorted(resps) != sorted(names) or \
            any(r.status != "ok" for r in resps.values()):
        raise AssertionError(
            f"paged: lost or failed requests: sent {sorted(names)}, "
            f"terminals {[(r.request_id, r.status) for r in resps.values()]}")
    tok = HashTokenizer(engine.cfg.vocab_size)
    for rid, (name, m) in names.items():
        r = resps[rid]
        ids = tok.encode(prompts[name])
        ref = engine.generate_reference(ids, max_new_tokens=m)
        got = token_ids(r.text)
        first = first_divergence(got, ref["tokens"])
        noise = 0.0 if first is None else logit_noise(
            engine, ids, ref["tokens"][:first + 1], plan.lanes)
        verdict = check_tokens(name, got, ref, noise)
        say(f"paged: request {name} rid={rid} prompt_tokens={len(ids)} "
            f"asked={m} got={r.tokens_generated} ttft_s={r.ttft_s} "
            f"queue_s={r.queue_wait_s} service_s={r.service_s}: {verdict}")
    st = paged.engine_stats()
    if st["prefix_hit_pages"] <= 0:
        raise AssertionError(f"paged: no prefix-cache hit ({st})")
    say(f"paged: prefix_hits={st['prefix_hits']} "
        f"prefix_hit_pages={st['prefix_hit_pages']} "
        f"preemptions={paged.lane_manager.stats.get('preemptions')} "
        f"dead_steps={st['dead_steps']}")
    return {"requests": len(resps), "prefix_hit_pages": st["prefix_hit_pages"]}


# ------------------------------------------------------------------- main
def main(plan: Plan = CHIP_PLAN) -> int:
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: JAX reports platform {dev['platform']!r} "
              f"({dev['kind']}), not a TPU; nothing was built",
              file=sys.stderr)
        return 1
    say(f"smoke run, not a benchmark: {dev['kind']} x {dev['count']}; "
        f"{versions()}")
    from repro.core._native import native_scorer
    from repro.launch.compile_cache import setup_compile_cache
    cache = setup_compile_cache()
    say(f"compile cache {cache}: {cache_entries(cache)} entries before")
    say("admission scorer: " + ("native C" if native_scorer() is not None
                                else "numpy fallback"))
    say("token checks: bitwise up to the first differing token, which must "
        "be a near-tie of the serial oracle (top-2 logit gap below bf16 "
        "resolution, or below twice the logit noise, up to that step, "
        f"between its one-row and {plan.lanes}-row programs)")
    meter = CompileMeter().install()
    with donation_fails():
        snap = meter.snapshot()
        _, engine, predictor = sidecar_phase(plan)
        say(f"sidecar phase: {meter.since(snap)} "
            f"peak_bytes_in_use={peak_bytes()}")
        snap = meter.snapshot()
        paged_phase(plan, engine, predictor)
        say(f"paged phase: {meter.since(snap)} "
            f"peak_bytes_in_use={peak_bytes()}")
    say(f"compile cache {cache}: {cache_entries(cache)} entries after")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
