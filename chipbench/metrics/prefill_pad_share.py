"""Engine (``serving/engine.py``): the share of the window's prefill
positions that were pads, in %: bucket length less prompt length, summed
over the program's ``prefill`` regions (their ``tokens`` and ``bucket``
args), over the bucket lengths summed.  A pad is computed and then masked
out of every state, so the share is prefill work that serves no token.
Moves ``ttft_short_p50_s``.  Nothing is read where the regions carry no
``bucket``."""


def read(run):
    pads = total = 0
    for s in run.spans:
        if s.name == "prefill" and s.args and "bucket" in s.args:
            pads += s.args["bucket"] - s.args["tokens"]
            total += s.args["bucket"]
    return 100.0 * pads / total if total else None
