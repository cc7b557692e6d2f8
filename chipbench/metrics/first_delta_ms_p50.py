"""Wire front end (``serving/http_sidecar.py``): the program's share of
the first token's way to the client, from its spans: the end of
``prefill`` (the first token on the host) to the end of the first
``sse_write`` (``seg`` 0: the write and drain of the first delta), median
over the window's completed streamed requests, in ms.  The rest of
``wire_ttft_overhead_ms`` is the client and the loopback.  Moves
``ttft_short_p50_s``."""

from chipbench.stats import median


def read(run):
    prefill_end, first_write = {}, {}
    for s in run.spans:
        if s.name == "prefill" and s.req_id not in prefill_end:
            prefill_end[s.req_id] = s.t1
        elif s.name == "sse_write" and s.args and s.args.get("seg") == 0:
            first_write[s.req_id] = s.t1
    vals = [1e3 * (t - prefill_end[rid]) for rid, t in first_write.items()
            if rid in prefill_end and rid in run.responses
            and run.responses[rid].ok]
    return median(vals) if vals else None
