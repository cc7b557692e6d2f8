"""Reduce a profiler trace to device busy time, top ops and idle gaps.

Input is the ``.xplane.pb`` that ``jax.profiler`` writes.  On a TPU the
device ops are the events of each ``/device:TPU:<n>`` plane's ``XLA Ops``
line and the programs those of its ``XLA Modules`` line.  On the CPU
backend (the recorded test trace) they are the host events that carry an
``hlo_op`` stat, and a program is the span of one ``(hlo_module, run_id)``.
The harness's own spans are host events named ``chipbench.<what>``.

* Busy time is the union of a device's op intervals inside the window
  (ops nest: a ``while`` op contains the ops of its body), averaged over
  devices.  The window is the ``chipbench.window`` span.
* An op's time is its self time: its duration less that of the ops nested
  in it.  Ops are named ``<program>/<op>``.
* An idle gap is a stretch of the window with no op running on the first
  device.  One of ``MIN_GAP_NS`` or more is named by the innermost harness
  span around its midpoint, or ``idle: outside any harness span``; the
  shorter ones, between ops of one program, are summed under
  ``idle: gaps under 10 us``.
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from dataclasses import dataclass, field

PREFIX = "chipbench."
WINDOW = "chipbench.window"
OUTSIDE = "idle: outside any harness span"
SHORT_GAPS = "idle: gaps under 10 us"
MIN_GAP_NS = 1e4
TOP = 10


@dataclass
class Interval:
    name: str
    start: float                 # ns, on the trace's clock
    end: float
    args: dict = field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    ops: dict          # device -> [Interval] sorted by start
    modules: dict      # device -> [Interval] sorted by start
    marks: list        # harness spans, sorted by start


def _short_op(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%")


def _short_module(name: str) -> str:
    return name.split("(", 1)[0]


def read(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, marks = defaultdict(list), defaultdict(list), []
    tpu = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    for plane in tpu:
        for line in plane.lines:
            if line.name == "XLA Ops":
                dst = ops[plane.name]
            elif line.name == "XLA Modules":
                dst = modules[plane.name]
            else:
                continue
            dst.extend(Interval(e.name, e.start_ns, e.end_ns)
                       for e in line.events)
    cpu_modules = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    marks.append(Interval(e.name, e.start_ns, e.end_ns,
                                          dict(e.stats)))
                elif not tpu:
                    st = dict(e.stats)
                    if "hlo_op" not in st:
                        continue
                    ops[plane.name].append(Interval(
                        f"%{st['hlo_op']} = ", e.start_ns, e.end_ns))
                    key = (plane.name, st.get("hlo_module", "?"),
                           st.get("run_id", 0))
                    m = cpu_modules.get(key)
                    if m is None:
                        cpu_modules[key] = Interval(key[1], e.start_ns,
                                                    e.end_ns)
                    else:
                        m.start = min(m.start, e.start_ns)
                        m.end = max(m.end, e.end_ns)
    for (dev, _, _), m in cpu_modules.items():
        modules[dev].append(m)
    for d in (ops, modules):
        for v in d.values():
            v.sort(key=lambda i: (i.start, -i.end))
    marks.sort(key=lambda i: i.start)
    return Trace(dict(ops), dict(modules), marks)


def window(tr: Trace) -> tuple:
    """(start, end) in ns: the harness's window span, else the ops' extent."""
    for m in tr.marks:
        if m.name == WINDOW:
            return m.start, m.end
    allops = [i for v in tr.ops.values() for i in v]
    if not allops:
        raise ValueError("trace holds no device op and no window span")
    return min(i.start for i in allops), max(i.end for i in allops)


def union(intervals, t0: float, t1: float) -> list:
    """Merged ``(start, end)`` pairs of the intervals, clipped to [t0, t1]."""
    out = []
    for i in sorted(intervals, key=lambda i: i.start):
        s, e = max(i.start, t0), min(i.end, t1)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(p) for p in out]


def _module_of(modules: list, starts: list, t: float) -> str:
    k = bisect.bisect_right(starts, t) - 1
    if k >= 0 and modules[k].end >= t:
        return _short_module(modules[k].name)
    return "?"


def self_times(ops: list, modules: list, t0: float, t1: float) -> dict:
    """Self time per ``<program>/<op>`` of the ops that start in the window
    (ns)."""
    out = defaultdict(float)
    starts = [m.start for m in modules]

    def close(done, child):
        out[_module_of(modules, starts, done.start) + "/"
            + _short_op(done.name)] += done.dur - child

    stack = []                     # [interval, time of its children]
    for i in (i for i in ops if t0 <= i.start < t1):
        while stack and stack[-1][0].end <= i.start:
            close(*stack.pop())
        if stack:
            stack[-1][1] += i.dur
        stack.append([i, 0.0])
    for done, child in stack:
        close(done, child)
    return out


def label(marks: list, t: float) -> str:
    """Name of the innermost harness span (not the window) around t."""
    best = None
    for m in marks:
        if m.start > t:
            break
        if m.name != WINDOW and m.end >= t \
                and (best is None or m.dur < best.dur):
            best = m
    return best.name if best is not None else OUTSIDE


def reduce(tr: Trace) -> dict:
    """busy_s, window_s, the top device ops by self time, the idle time
    summed by what the host was doing (``idle_gaps``) and the longest
    single idle gaps."""
    t0, t1 = window(tr)
    devices = sorted(tr.ops)
    if not devices:
        raise ValueError("trace holds no device op")
    busy = {d: union(tr.ops[d], t0, t1) for d in devices}
    busy_ns = sum(sum(e - s for s, e in busy[d]) for d in devices) \
        / len(devices)
    first = devices[0]
    selfs = self_times(tr.ops[first], tr.modules.get(first, []), t0, t1)
    top = sorted(selfs.items(), key=lambda kv: -kv[1])[:TOP]
    gaps, prev = [], t0
    for s, e in busy[first] + [(t1, t1)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    named = sorted(((label(tr.marks, (a + b) / 2), (b - a) / 1e9)
                    for a, b in gaps if b - a >= MIN_GAP_NS),
                   key=lambda g: -g[1])
    by_label = defaultdict(float)
    for name, sec in named:
        by_label[name] += sec
    by_label[SHORT_GAPS] = sum(b - a for a, b in gaps
                               if b - a < MIN_GAP_NS) / 1e9
    return {"busy_s": busy_ns / 1e9, "window_s": (t1 - t0) / 1e9,
            "device_ops": [[n, v / 1e9] for n, v in top],
            "idle_gaps": sorted(([n, v] for n, v in by_label.items()),
                                key=lambda g: -g[1])[:TOP],
            "longest_gaps": [[n, s] for n, s in named[:TOP]]}


def device_time_by_mark(tr: Trace, kinds: tuple) -> list:
    """``(mark, device ns)`` for each harness span of the given names that
    started in the window: the programs on the first device that ended
    after it started and before the next such span started, and ended
    inside the window.  A span whose programs were cut by the window's
    edge is left out.

    A program is placed by its end, not its start: on the TPU the device's
    clock in the trace runs ahead of the host's by a millisecond or two,
    so a program can seem to start before the span whose call launched it,
    while it still ends before the host, which waits for its result,
    starts the next span."""
    t0, t1 = window(tr)
    devices = sorted(tr.modules)
    if not devices:
        return []
    mods = tr.modules[devices[0]]
    marks = [m for m in tr.marks if m.name in kinds and t0 <= m.start < t1]
    starts = [m.start for m in marks]
    time = defaultdict(float)
    cut = set()
    for mod in mods:
        k = bisect.bisect_right(starts, mod.end) - 1
        if k < 0:
            continue
        if mod.end > t1:
            cut.add(k)
            continue
        time[k] += mod.dur
    return [(marks[k], time[k]) for k in sorted(time) if k not in cut]
