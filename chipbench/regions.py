"""The program's own host regions in a profiler trace, and what they name.

With a flight recorder attached, the serve path times its host work in
regions (``repro.serving.observability``): each is a host event named
``clairvoyant.<what>`` carrying its ``req_id`` and arguments, on the
thread that did the work, on the same clock as the device ops.  The
engine's worker thread runs ``prefill`` and ``decode_segment`` with the
decode loop's ``decode_poll`` / ``decode_dispatch`` / ``decode_sync`` /
``decode_emit`` / ``decode_stop`` inside; the event loop runs ``dispatch``, ``finish``,
``sse_write`` and the admission stages.

This module reads those events (``trace_reduce.read`` collects only the
harness's ``chipbench.*`` spans), names the chip's idle gaps by them, and
measures the device gap between the decode segments of one request.

    python3 chipbench/regions.py [trace.xplane.pb]

prints the idle time of the last traced run (or of the given trace) by
program region, each region's median duration and the segment gaps, as
JSON.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import sys
from collections import defaultdict
from dataclasses import dataclass

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chipbench import trace_reduce  # noqa: E402
from chipbench.stats import median  # noqa: E402
from chipbench.trace_reduce import Interval  # noqa: E402

PREFIX = "clairvoyant."
#: regions only the engine's worker thread runs
WORKER = ("clairvoyant.prefill", "clairvoyant.decode_segment")
SEGMENT = "clairvoyant.decode_segment"


@dataclass
class Region(Interval):
    thread: tuple = ()          # (plane name, line index)


def read(path: str) -> list:
    """Every ``clairvoyant.*`` host event of the trace, sorted by start."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            out.extend(Region(e.name, e.start_ns, e.end_ns, dict(e.stats),
                              (plane.name, k))
                       for e in line.events if e.name.startswith(PREFIX))
    out.sort(key=lambda r: (r.start, -r.end))
    return out


def last_trace():
    """The ``.xplane.pb`` the harness's last traced run wrote, or None."""
    from chipbench.run import TRACE_DIR
    found = glob.glob(os.path.join(TRACE_DIR, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def worker_threads(regions: list) -> set:
    return {r.thread for r in regions if r.name in WORKER}


def innermost(regions: list, t: float, threads=None):
    """The shortest region open at ``t`` (on one of ``threads``)."""
    best = None
    for r in regions:
        if r.start > t:
            break
        if r.end >= t and (threads is None or r.thread in threads) \
                and (best is None or r.dur < best.dur):
            best = r
    return best


def idle_gaps(tr) -> list:
    """``(start, end)`` of the stretches of the window with no op running
    on the first device."""
    t0, t1 = trace_reduce.window(tr)
    first = sorted(tr.ops)[0]
    gaps, prev = [], t0
    for s, e in trace_reduce.union(tr.ops[first], t0, t1) + [(t1, t1)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    return gaps


def idle_by_region(tr, regions: list) -> dict:
    """Idle seconds by what the host was doing at each gap's midpoint: the
    innermost program region open on the engine's worker thread, else on
    any thread, else the harness span (``trace_reduce.label``); gaps under
    10 us are summed apart, as in ``trace_reduce.reduce``."""
    worker = worker_threads(regions)
    out = defaultdict(float)
    for a, b in idle_gaps(tr):
        if b - a < trace_reduce.MIN_GAP_NS:
            out[trace_reduce.SHORT_GAPS] += (b - a) / 1e9
            continue
        mid = (a + b) / 2
        r = innermost(regions, mid, worker) or innermost(regions, mid)
        name = r.name if r is not None else trace_reduce.label(tr.marks, mid)
        out[name] += (b - a) / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def segment_gaps_ns(tr, regions: list) -> list:
    """For consecutive ``decode_segment`` regions k, k+1 of one request,
    both started in the window: the device's idle time from the end of the
    last program launched in k to the start of the first program launched
    in k+1 (a program is launched in a region when it starts on the first
    device between the region's start and end)."""
    t0, t1 = trace_reduce.window(tr)
    first = sorted(tr.modules)[0] if tr.modules else None
    if first is None:
        return []
    mods = tr.modules[first]
    ops = tr.ops[first]
    op_starts = [o.start for o in ops]
    longest = max((o.dur for o in ops), default=0.0)
    by_req = defaultdict(list)
    for r in regions:
        if r.name == SEGMENT and t0 <= r.start < t1:
            by_req[r.args.get("req_id")].append(r)
    gaps = []
    for segs in by_req.values():
        for k, k1 in zip(segs, segs[1:]):
            ends = [m.end for m in mods if k.start <= m.start <= k.end]
            starts = [m.start for m in mods if k1.start <= m.start <= k1.end]
            if not ends or not starts or min(starts) < max(ends):
                continue
            a, b = max(ends), min(starts)
            # only ops that start before b and may still run at a
            near = ops[bisect.bisect_left(op_starts, a - longest):
                       bisect.bisect_left(op_starts, b)]
            busy = sum(e - s for s, e in trace_reduce.union(near, a, b))
            gaps.append(b - a - busy)
    return gaps


def region_ms_p50(regions: list) -> dict:
    """``{region: [median duration in ms, count]}``."""
    durs = defaultdict(list)
    for r in regions:
        durs[r.name].append(r.dur / 1e6)
    return {k: [median(v), len(v)] for k, v in sorted(durs.items())}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    path = argv[0] if argv else last_trace()
    if path is None:
        print("no trace found", file=sys.stderr)
        return 1
    tr = trace_reduce.read(path)
    regions = read(path)
    named = idle_by_region(tr, regions)
    long_ = {k: v for k, v in named.items() if k != trace_reduce.SHORT_GAPS}
    total = sum(long_.values())
    print(json.dumps({
        "trace": path, "regions": len(regions),
        "idle_s_by_region": named,
        "outside_share_of_long_gaps": (
            long_.get(trace_reduce.OUTSIDE, 0.0) / total if total else None),
        "region_ms_p50": region_ms_p50(regions),
        "segment_gap_us": sorted(g / 1e3 for g in segment_gaps_ns(
            tr, regions))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
