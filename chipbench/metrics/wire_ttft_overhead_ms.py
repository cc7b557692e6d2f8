"""Wire front end (``serving/http_sidecar.py``): client-side TTFT less the
server's own TTFT for the same request, median over the window's completed
requests, in ms.

The server's TTFT is ``CompletionResponse.ttft_s``: from admission on the
server's clock to the engine's first token on the host (queue wait,
prefill and the first argmax).  What is left is the generator's lateness,
the connection, request parsing and scoring, the hand-offs between the
engine's thread and the event loop, and the SSE write and read.  Moves
``ttft_short_p50_s``.
"""

from chipbench.stats import median


def read(run):
    vals = [1e3 * ((r.t_first - r.due) - run.responses[r.request_id].ttft_s)
            for r in run.records
            if r.ok and r.request_id in run.responses
            and run.responses[r.request_id].ttft_s is not None]
    return median(vals) if vals else None
