"""Admission (``core/features.py``, ``core/predictor.py``): feature
extraction plus predictor scoring per request, from the FlightRecorder's
``feature_extract`` and ``predict`` spans, median over the window's
requests, in us.  Moves ``ttft_short_p50_s``."""

from collections import defaultdict

from chipbench.stats import median


def read(run):
    per = defaultdict(float)
    for s in run.spans:
        if s.name in ("feature_extract", "predict"):
            per[s.req_id] += s.dur
    return median([1e6 * v for v in per.values()]) if per else None
