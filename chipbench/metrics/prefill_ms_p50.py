"""Engine (``serving/engine.py``): the engine's own time to first token,
bucketed prefill plus the first argmax on the host
(``CompletionResponse.ttft_s`` less its ``queue_wait_s``), median over the
window's completed requests, in ms.  Moves ``ttft_short_p50_s``."""

from chipbench.stats import median


def read(run):
    vals = [1e3 * (r.ttft_s - r.queue_wait_s) for r in run.responses.values()
            if r.ok and r.ttft_s is not None]
    return median(vals) if vals else None
