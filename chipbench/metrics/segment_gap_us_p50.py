"""Decode loops (``serving/generate.py``): the chip's idle time between
consecutive decode segments of one request, from the program's own
``clairvoyant.decode_segment`` regions in the trace
(``regions.segment_gaps_ns``: the end of the last program launched in
segment k to the start of the first launched in k+1), median, in us.
Moves ``tpot_p90_ms``."""

from chipbench import regions
from chipbench.stats import median


def read(run):
    if run.trace is None:
        return None
    path = regions.last_trace()
    if path is None:
        return None
    gaps = regions.segment_gaps_ns(run.trace, regions.read(path))
    return median([g / 1e3 for g in gaps]) if gaps else None
