"""What decides ``correct``: the wire, the dispatch order, the tokens.

* Wire and queue: every request sent and answered came back HTTP 200 as
  one SSE stream ending in ``stop`` and ``[DONE]``, with exactly the tokens
  it asked for, each a valid id.  A request still open when the drain cap
  ran out is late (``failed``), not wrong.
* Admission and policy: at each dispatch decision the queue chose what the
  policy's rule, applied here independently, chooses from the same waiting
  requests, arrival times and predictions.  For ``sjf`` (the paper's rule,
  ``core/policy.py`` and ``core/scheduler.py``): the oldest waiter if it
  has waited more than tau, else the lowest P(Long), ties to the earlier
  admission (the lower id; ids are given out in admission order).
* Engine and model: for a sample of finished requests drawn from the seed,
  with the longest among them, the reference (``reference.py``) runs over
  each prompt with its served tokens; the widest gap by which a served
  token's reference logit lies below the reference's best at that position
  is held to the configuration's limit.
"""

from __future__ import annotations

import numpy as np

#: the sample holds the longest finished request and at least this many
#: requests and served tokens, where the window finished as many
SAMPLE_REQUESTS = 4
SAMPLE_TOKENS = 600


def served_ids(rec, vocab: int):
    """The served token ids of a streamed answer, or None if the text is
    not a list of ``t<id>`` words."""
    out = []
    for w in rec.text.split():
        if not (w.startswith("t") and w[1:].isdigit()):
            return None
        out.append(int(w[1:]))
    ids = np.asarray(out, np.int64)
    return ids if len(ids) and ids.max() < vocab else None


def wire_faults(records, vocab: int) -> tuple:
    """(answers that are not a clean stream, answers of the wrong length).
    Requests that never answered (``status`` None) are left to
    ``failed``."""
    bad, wrong = 0, 0
    for r in records:
        if r.status is None:
            continue
        if not r.ok:
            bad += 1
            continue
        ids = served_ids(r, vocab)
        if ids is None or len(ids) != r.max_tokens:
            wrong += 1
    return bad, wrong


def sjf_choice(waiting, now: float, tau) -> int:
    """The id the paper's rule dispatches from ``waiting`` =
    [(id, arrival, p_long)]."""
    oldest = min(waiting, key=lambda w: w[0])
    if tau is not None and now - oldest[1] > tau:
        return oldest[0]
    return min(waiting, key=lambda w: (w[2], w[0]))[0]


def dispatch_faults(pops, policy: str) -> int:
    """Dispatch decisions that break the policy's rule."""
    if policy != "sjf":
        raise ValueError(f"no independent rule for policy {policy!r}")
    return sum(1 for p in pops
               if sjf_choice(p["waiting"], p["now"], p["tau"])
               != p["chosen"])


def sample(records, seed: int, vocab: int) -> list:
    """Finished requests to hold against the reference: the longest, then
    others in an order drawn from the seed."""
    done = [r for r in records if r.ok and served_ids(r, vocab) is not None]
    if not done:
        return []
    done.sort(key=lambda r: (-r.n_tokens, r.index))
    rest = done[1:]
    order = np.random.default_rng(seed).permutation(len(rest))
    picked, tokens = [done[0]], done[0].n_tokens
    for j in order:
        if len(picked) >= SAMPLE_REQUESTS and tokens >= SAMPLE_TOKENS:
            break
        picked.append(rest[j])
        tokens += rest[j].n_tokens
    return picked
