"""Open-loop traffic from a mix file: due times, prompts, output lengths.

A mix is ``chipbench/traffic/<name>.json``; its prompt and length model is
``chipbench/profiles/<profile>.json``.  The prompt and length generation is
a copy of ``src/repro/data/corpus.py`` (class -> lexical features -> prompt
text -> response length), kept here so that a change to the program's
corpus cannot move the benchmark.

Every request of a mix, and every arrival time, is drawn from the mix's
``pool_seed``: each seed gets the same schedule, and ``--seed`` makes the
weights, and so every served token.  Over a window of under a minute the
TTFT tails of one arrival pattern differ from another's by far more than
a bound of 25% could admit (a third to nine tenths, IQR over median, in a
queueing model of the oasst1 cell), so the cells measure the system on
one pattern.

Mix keys:

* ``profile``: the profile file's name.
* ``classes``: the true classes sent (``short`` is under 200 output tokens).
* ``arrivals``: ``{"kind": "poisson", "rate_per_s": r}`` or
  ``{"kind": "burst", "size": n, "period_s": p}`` (a burst at the start of
  the window and every ``p`` seconds after).
* ``pool_seed``: the seed of the arrival times, of the request pool and
  of which request goes to which arrival.
* ``max_tokens_cap``: output lengths above it are cut to it (each request's
  prompt plus output must fit the configuration's ``max_len``).
* ``drain_cap_s``: how long requests already sent are awaited after the
  window closes; one still open then has failed.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

CLASSES = ("short", "medium", "long")

INSTRUCTION_VERBS = (
    "what", "write", "explain", "summarize", "how", "list", "implement",
    "compare", "describe", "generate", "why", "define",
)

_TOPICS = (
    "the french revolution", "binary search trees", "photosynthesis",
    "the stock market", "quantum entanglement", "sourdough bread",
    "the roman empire", "neural networks", "climate change", "chess openings",
    "the water cycle", "renaissance art", "black holes", "supply chains",
    "genetic drift", "jazz harmony", "plate tectonics", "game theory",
    "the immune system", "medieval castles", "rust ownership",
    "distributed systems", "the krebs cycle", "haiku poetry",
    "orbital mechanics", "tax law", "coffee roasting", "graph colouring",
    "marine ecosystems", "the printing press",
)

_FILLERS = (
    "please", "kindly", "also", "specifically", "ideally", "overall",
    "for context", "as an expert", "for a beginner", "for my homework",
    "for work", "in simple terms", "with examples", "from first principles",
    "carefully", "quickly", "roughly", "accurately",
)

_CLAUSES = (
    "because i need it for a project", "which i find confusing",
    "that my teacher mentioned", "since i am new to this",
    "although i read the wiki", "when i tried it before",
    "if that makes sense", "where it applies in practice",
    "because the documentation is unclear", "which keeps coming up at work",
)

_CODE_SNIPPETS = (
    "a python function", "a javascript class", "an sql query",
    "a sorting algorithm", "a regex pattern", "an api client",
    "a c++ program", "a shell script",
)

_FORMAT_ASKS = (
    "as a table", "as a json object", "as a markdown list",
    "as a csv file", "as a numbered list", "in yaml format",
)

_SHORT_CONSTRAINTS = ("briefly", "in one sentence", "be concise", "tl;dr",
                      "short answer only")
_LONG_CONSTRAINTS = ("in detail", "comprehensive", "step by step",
                     "at length", "as an essay with paragraphs")


@dataclass(frozen=True)
class Request:
    """One scheduled request: due ``offset_s`` after the window opens."""
    offset_s: float
    prompt: str
    max_tokens: int
    klass: str
    cut: bool               # output length was cut to ``max_tokens_cap``


def load_json(kind: str, name: str) -> dict:
    """``chipbench/<kind>/<name>.json``."""
    with open(os.path.join(HERE, kind, f"{name}.json")) as f:
        return json.load(f)


# ------------------------------------------------------------------ corpus
def _sample_verb(rng, prof: dict, k: int) -> str:
    if rng.random() < 0.08:
        return rng.choice(["craft", "outline", "ponder", "sketch", "assess"])
    if rng.random() > prof["verb_strength"]:
        return INSTRUCTION_VERBS[int(rng.integers(0, len(INSTRUCTION_VERBS)))]
    pairs = prof["verb_affinity"][CLASSES[k]]
    idx = np.array([INSTRUCTION_VERBS.index(v) for v, _ in pairs])
    w = np.array([wt for _, wt in pairs])
    return INSTRUCTION_VERBS[rng.choice(idx, p=w / w.sum())]


def _leak_class(rng, k: int, prof: dict) -> int:
    u = rng.random()
    if u < prof["noise_uniform"]:
        return int(rng.integers(0, 3))
    if u < prof["noise_uniform"] + prof["noise_adjacent"]:
        if k == 1:
            return 0 if rng.random() < 0.5 else 2
        return 1
    return k


def gen_prompt(rng, prof: dict, k: int) -> str:
    fk = _leak_class(rng, k, prof)
    verb = _sample_verb(rng, prof, fk)
    parts = [verb.capitalize()]
    if rng.random() < prof["code_prob"][fk]:
        parts.append(rng.choice(_CODE_SNIPPETS) + " for")
    parts.append(rng.choice(_TOPICS))
    if rng.random() < prof["format_prob"][fk]:
        parts.append(rng.choice(_FORMAT_ASKS))
    if rng.random() < prof["constraint_prob"][fk]:
        parts.append(rng.choice(_LONG_CONSTRAINTS if fk == 2
                                else _SHORT_CONSTRAINTS))
    for _ in range(min(rng.poisson(prof["clause_rate"][fk]), 3)):
        parts.append(rng.choice(_CLAUSES))
    target = max(4, int(rng.normal(prof["words_mean"][fk],
                                   prof["words_std"][fk])))
    words = " ".join(parts).split()
    while len(words) < target:
        words.append(rng.choice(_FILLERS))
    text = " ".join(words)
    if rng.random() < prof["question_prob"][fk]:
        text = text + "?"
    return text


def gen_length(rng, prof: dict, k: int) -> int:
    if k == 0:
        return int(np.clip(rng.lognormal(3.7, 0.8), 1, 199))
    if k == 1:
        return int(rng.integers(200, 800))
    mu = np.log(prof["response_long_mean"])
    return int(np.clip(rng.lognormal(mu, 0.45), 800, 8000))


def class_probs(prof: dict, classes) -> np.ndarray:
    c = np.array([prof["class_counts"][k] for k in classes], float)
    return c / c.sum()


# ---------------------------------------------------------------- schedule
def arrival_offsets(arrivals: dict, seconds: float, seed: int) -> np.ndarray:
    """Due offsets in ``[0, seconds)``, ascending."""
    if arrivals["kind"] == "poisson":
        rng = np.random.default_rng(seed)
        rate = float(arrivals["rate_per_s"])
        out, t = [], rng.exponential(1.0 / rate)
        while t < seconds:
            out.append(t)
            t += rng.exponential(1.0 / rate)
        return np.asarray(out)
    if arrivals["kind"] == "burst":
        starts = np.arange(0.0, seconds, float(arrivals["period_s"]))
        return np.repeat(starts, int(arrivals["size"]))
    raise ValueError(f"unknown arrival kind {arrivals['kind']!r}")


def class_counts(n: int, probs: np.ndarray) -> np.ndarray:
    """Largest-remainder split of ``n`` requests by class share."""
    raw = n * probs
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return counts


def pool(mix: dict, n: int) -> list:
    """The mix's ``n`` requests as ``(prompt, output_len, klass)``, with each
    class's share of ``n`` as close as whole requests allow."""
    prof = load_json("profiles", mix["profile"])
    classes = tuple(mix["classes"])
    rng = np.random.default_rng(int(mix["pool_seed"]) + 1)
    out = []
    for klass, cnt in zip(classes, class_counts(n, class_probs(prof,
                                                               classes))):
        k = CLASSES.index(klass)
        for _ in range(cnt):
            out.append((gen_prompt(rng, prof, k), gen_length(rng, prof, k),
                        klass))
    return out


def schedule(mix: dict, seconds: float) -> list:
    """The run's requests, in due order."""
    offsets = arrival_offsets(mix["arrivals"], seconds, int(mix["pool_seed"]))
    reqs = pool(mix, len(offsets))
    order = np.random.default_rng(int(mix["pool_seed"]) + 2).permutation(
        len(reqs))
    cap = int(mix["max_tokens_cap"])
    out = []
    for off, j in zip(offsets, order):
        prompt, length, klass = reqs[j]
        out.append(Request(float(off), prompt, min(length, cap), klass,
                           length > cap))
    return out


def prompt_tokens(prompt: str) -> int:
    """Prompt length in tokens: the served tokenizer maps each
    whitespace-separated word to one id."""
    return len(prompt.split())
