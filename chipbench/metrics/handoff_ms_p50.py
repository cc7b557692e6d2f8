"""Serve loop (``serving/http_sidecar.py`` ``_dispatch_loop`` /
``_serve_one``, ``serving/backends.py``): the hand-off from one request
to the next on a backend that had work waiting, from the program's spans.
For each request that arrived (its ``queue_wait`` start) before the
previous request's last ``decode_segment`` ended: from that end to the
start of this request's ``prefill``.  Median over the window, in ms.
Read only where the program times its ``dispatch`` region, so that the
segment and prefill spans are measured ones.  Moves
``ttft_short_p50_s``."""

from collections import defaultdict

from chipbench.stats import median


def read(run):
    if not any(s.name == "dispatch" for s in run.spans):
        return None
    arrival, prefill, seg_end, track = {}, {}, {}, {}
    for s in run.spans:
        rid = s.req_id
        if s.name == "queue_wait":
            arrival[rid] = s.t0
        elif s.name == "prefill" and (rid not in prefill
                                      or s.t0 < prefill[rid]):
            prefill[rid], track[rid] = s.t0, s.track
        elif s.name == "decode_segment":
            seg_end[rid] = max(s.t1, seg_end.get(rid, s.t1))
    by_track = defaultdict(list)
    for rid in prefill:
        by_track[track[rid]].append(rid)
    vals = []
    for rids in by_track.values():
        rids.sort(key=prefill.get)
        for prev, cur in zip(rids, rids[1:]):
            end = seg_end.get(prev)
            if end is not None and cur in arrival and arrival[cur] < end:
                vals.append(1e3 * (prefill[cur] - end))
    return median(vals) if vals else None
