"""Queue / policy (``core/scheduler.py``, ``core/policy.py``): 90th
percentile of ``CompletionResponse.queue_wait_s`` over the window's
completed Short requests (true class), in s.  Moves
``ttft_short_p90_s``."""

from chipbench.stats import percentile


def read(run):
    vals = [r.queue_wait_s for r in run.responses.values()
            if r.ok and r.klass == "short"]
    return percentile(vals, 90) if vals else None
