"""From the profiler's trace to numbers: busy and idle time of the device,
time by operation and by program, and each idle gap attributed to what the
host was doing.

Input is what ``jax.profiler.ProfileData.from_file(<.xplane.pb>)`` gives
(or anything shaped like it: ``planes`` with ``name`` and ``lines``, lines
with ``name`` and ``events``, events with ``name``, ``start_ns`` and
``duration_ns``). A device plane is named ``/device:TPU:<n>``; its line
``XLA Ops`` holds one event per operation that ran and ``XLA Modules`` one
per program. The host's planes hold the harness's own spans, whose names
start with ``span_prefix``; the span ``<prefix>traced_window`` is the window.

    busy      the union of the intervals in which an operation ran, cut to
              the window, averaged over the device planes used
    idle      window - busy; each gap goes to the harness span (other than
              the window's) that covers most of it, else to "unattributed"
"""
from __future__ import annotations

import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WINDOW_SPAN = "traced_window"


def union(intervals):
    """Merged, sorted list of (start, end) from any list of them."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals):
    return sum(e - s for s, e in intervals)


def gaps(busy, lo, hi):
    """The complement of a merged interval list inside [lo, hi]."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def overlap(a, b):
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


def short_name(name: str) -> str:
    """The profiler names a device operation by its whole HLO line
    (``%copy.44.remat = bf16[..]{layout} copy(...)``). Keep the instruction's
    name; the result type without layouts goes behind it, so that two
    kernels that XLA both calls ``closed_call.N`` stay apart."""
    if " = " not in name:
        return name
    head, rest = name.split(" = ", 1)
    head = head.lstrip("%")
    m = re.match(r"(\(.*?\)|\S+) [\w-]+\(", rest)
    shape = re.sub(r"\{[^{}]*\}", "", m.group(1)) if m else ""
    return (head + " " + shape).strip()[:96]


def base_name(name: str) -> str:
    """A short name without XLA's numbering: ``fusion.123`` and
    ``fusion.7`` are one row of the breakdown."""
    head, _, shape = name.partition(" ")
    return (re.sub(r"\.\d+", "", head) + " " + shape).strip()


def self_times(events):
    """(name, start, duration) events of ONE line, which nest (a ``while``
    holds its body's operations): yields (name, self_ns), the duration less
    what the operations inside it took."""
    stack = []                      # [name, end, self]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            done = stack.pop()
            yield done[0], done[2]
        if stack:
            stack[-1][2] -= d
        stack.append([name, s + d, d])
    while stack:
        done = stack.pop()
        yield done[0], done[2]


class TraceSummary:
    """All times in seconds."""

    def __init__(self):
        self.window_s = 0.0
        self.busy_s = 0.0
        self.n_devices = 0
        self.op_s = defaultdict(float)        # operation -> self seconds
        self.op_n = defaultdict(int)
        self.module_s = defaultdict(float)    # program name -> seconds
        self.module_n = defaultdict(int)
        self.gap_s = defaultdict(float)       # host span name -> idle s
        self.span_s = defaultdict(float)      # host span name -> seconds
        self.window_ns = (0, 0)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def ops_matching(self, pattern: str):
        """(seconds, count) of the operations whose name matches."""
        rx = re.compile(pattern)
        names = [n for n in self.op_s if rx.search(n)]
        return (sum(self.op_s[n] for n in names),
                sum(self.op_n[n] for n in names))

    def modules_matching(self, pattern: str):
        rx = re.compile(pattern)
        names = [n for n in self.module_s if rx.search(n)]
        return (sum(self.module_s[n] for n in names),
                sum(self.module_n[n] for n in names))

    def top_ops(self, n):
        grouped = defaultdict(float)
        for name, s in self.op_s.items():
            grouped[base_name(name)] += s
        return [[k, v] for k, v in sorted(grouped.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n):
        return [[k, v] for k, v in sorted(self.gap_s.items(),
                                          key=lambda kv: -kv[1])[:n]]


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            for ev in line.events:
                yield ev.name, int(ev.start_ns), int(ev.duration_ns)


def reduce(data, n_devices=1, span_prefix="bench."):
    devices = sorted((int(DEVICE_PLANE.match(p.name).group(1)), p)
                     for p in data.planes if DEVICE_PLANE.match(p.name))
    spans = []                                # (name, start, end), host side
    for p in data.planes:
        if DEVICE_PLANE.match(p.name):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name.startswith(span_prefix):
                    s = int(ev.start_ns)
                    spans.append((ev.name[len(span_prefix):], s,
                                  s + int(ev.duration_ns)))
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    out = TraceSummary()
    if not devices:
        raise ValueError("the trace holds no device plane (/device:TPU:n): "
                         "nothing ran on a chip while it was taken")
    if not windows:
        raise ValueError(f"the trace holds no {span_prefix}{WINDOW_SPAN} "
                         f"span")
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    out.window_ns = (lo, hi)
    out.window_s = (hi - lo) / 1e9
    used = devices[:n_devices]
    out.n_devices = len(used)
    host = [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN]
    for n, s, e in host:
        out.span_s[n] += overlap((s, e), (lo, hi)) / 1e9
    host.sort(key=lambda x: x[1])
    for _idx, plane in used:
        ops = [(short_name(n), s, d) for n, s, d in _events(plane, OPS_LINE)
               if overlap((s, s + d), (lo, hi)) > 0]
        ivals = [(s, s + d) for _n, s, d in ops]
        for name, self_ns in self_times(ops):
            out.op_s[name] += self_ns / 1e9 / len(used)
            out.op_n[name] += 1
        for name, s, d in _events(plane, MODULES_LINE):
            o = overlap((s, s + d), (lo, hi))
            if o > 0:
                out.module_s[name] += o / 1e9 / len(used)
                out.module_n[name] += 1
        busy = clip(union(ivals), lo, hi)
        out.busy_s += total(busy) / 1e9 / len(used)
        _attribute(gaps(busy, lo, hi), host, out.gap_s, len(used))
    return out


def _attribute(idle, host, gap_s, n_used):
    """Each idle gap goes, piece by piece, to the host spans that cover it
    (the innermost where they nest: the one that started last); what no
    span covers is ``unattributed``."""
    import bisect

    starts = [s for _, s, _ in host]
    for g0, g1 in idle:
        covered = 0
        # spans that can overlap the gap start before its end
        hi_i = bisect.bisect_left(starts, g1)
        pieces = []
        for name, s, e in host[max(0, hi_i - 64):hi_i]:
            o = overlap((s, e), (g0, g1))
            if o > 0:
                pieces.append((name, max(s, g0), min(e, g1)))
        # later-started spans win where they nest
        taken = []
        for name, s, e in reversed(pieces):
            free = gaps(union(taken), s, e)
            for fs, fe in free:
                gap_s[name] += (fe - fs) / 1e9 / n_used
                covered += fe - fs
            taken.append((s, e))
        rest = (g1 - g0) - covered
        if rest > 0:
            gap_s["unattributed"] += rest / 1e9 / n_used
