"""Decode loops (``serving/generate.py``): engine-side time per decoded
token, (``service_s`` - engine TTFT) / (tokens - 1), median over the
window's completed requests of two tokens or more, in ms.  Moves
``tpot_p90_ms``."""

from chipbench.stats import median


def read(run):
    vals = [1e3 * (r.service_s - (r.ttft_s - r.queue_wait_s))
            / (r.tokens_generated - 1) for r in run.responses.values()
            if r.ok and r.ttft_s is not None and r.tokens_generated >= 2]
    return median(vals) if vals else None
