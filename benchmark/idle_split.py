"""Where the device's idle time went, by the program's own spans: each idle
piece of a traced window goes to what the thread that drives the device was
doing, or to the program that was running.

It reads the same trace as ``trace_reduce.reduce`` and changes nothing of
what that gives (``gap_s`` keeps the harness's ``bench.`` spans alone and
gives a gap to the latest-started one on ANY host line). Here each idle
piece of each used device plane goes, by the first rule that takes it, to:

    (a) ``in_program``: the piece lies inside an ``XLA Modules`` interval of
        that plane, a gap between the operations of a running program;
    (b) the innermost span, the program's (``pt.``) or the harness's, open
        on the STEPPING thread: the host line that carries the most
        ``pt.serve.step`` / ``pt.train.step`` spans. A program span inside
        a ``pt.serve.step`` is keyed with the step's ``action``
        (``pt.serve.emit[decode]``); the step's own self time is
        ``pt.serve.step[decode]``;
    (c) the latest-started harness span open on any other line;
    (d) ``unattributed``.

The harness's window span is never a candidate. The sweep is exact: every
span of every line is read, however many came before a gap. The pieces sum
to window - busy.
"""
from __future__ import annotations

import heapq
import re
from bisect import bisect_right
from collections import Counter, defaultdict

from benchmark.trace_reduce import (DEVICE_PLANE, MODULES_LINE, OPS_LINE,
                                    WINDOW_SPAN, clip, gaps, total, union)

PROGRAM_PREFIX = "pt."          # paddle_tpu.profiler.SPAN_PREFIX
STEP_SPANS = ("pt.serve.step", "pt.train.step")
IN_PROGRAM = "in_program"
UNATTRIBUTED = "unattributed"

#: a serving step's phases, by the spans whose idle each takes (the key
#: without its ``[action]``); ``run`` holds the programs it calls
PHASES = {
    "plan": re.compile(r"^pt\.serve\.plan$"),
    "run": re.compile(r"^pt\.(?:serve\.run|program:.*)$"),
    "emit": re.compile(r"^pt\.serve\.emit$"),
    "program": re.compile("^" + IN_PROGRAM + "$"),
}


class IdleSplit:
    """All times in seconds, averaged over the device planes used."""

    def __init__(self):
        self.window_s = 0.0
        self.busy_s = 0.0
        self.idle_by_span = defaultdict(float)
        self.steps = Counter()      # action -> steps ended in the window
        self.spans_in_window = 0    # program spans begun on the stepping line
        self.stepping_line = None

    @property
    def span_n(self):
        """The steps that ended inside the window: the per-step
        denominator."""
        return sum(self.steps.values())

    def top(self, n):
        return [[k, v] for k, v in sorted(self.idle_by_span.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def _parts(self, key):
        """(span name, action or None) of a key."""
        for action in self.steps:
            if action is not None and key.endswith(f"[{action}]"):
                return key[:-len(action) - 2], action
        return key, None

    def per_step_ms(self, phase, action=None):
        """Milliseconds of device idle a step under ``phase``; with
        ``action``, over the steps of that action alone (``program``
        pieces carry none). None where the trace holds no such step."""
        n = self.steps[action] if action is not None else self.span_n
        if not n:
            return None
        rx = PHASES[phase]
        got = 0.0
        for key, v in self.idle_by_span.items():
            name, act = self._parts(key)
            if rx.match(name) and action in (None, act):
                got += v
        return 1e3 * got / n


def innermost(spans):
    """(start, end, key) spans, nested or not, to disjoint (start, end, key)
    pieces in time order, each piece given to the latest-started span open
    over it (the innermost where they nest; of two that start together,
    the shorter)."""
    spans = sorted((s, e, k) for s, e, k in spans if e > s)
    bounds = sorted({t for s, e, _ in spans for t in (s, e)})
    heap, out, i = [], [], 0
    for t0, t1 in zip(bounds, bounds[1:]):
        while i < len(spans) and spans[i][0] <= t0:
            s, e, k = spans[i]
            heapq.heappush(heap, (-s, e, i, k))
            i += 1
        while heap and heap[0][1] <= t0:
            heapq.heappop(heap)
        if not heap:
            continue
        _, _, idx, key = heap[0]
        if out and out[-1][3] == idx and out[-1][1] == t0:
            out[-1][1] = t1
        else:
            out.append([t0, t1, key, idx])
    return [(s, e, k) for s, e, k, _ in out]


def assign(pieces, segments, into, scale):
    """Give each part of ``pieces`` that a segment covers to the segment's
    key (seconds over ``scale``); returns the parts no segment covers. Both
    lists are disjoint and in time order."""
    rest, j = [], 0
    for p0, p1 in pieces:
        while j < len(segments) and segments[j][1] <= p0:
            j += 1
        at, k = p0, j
        while k < len(segments) and segments[k][0] < p1:
            s, e, key = segments[k]
            lo, hi = max(s, at), min(e, p1)
            if lo > at:
                rest.append((at, lo))
            if hi > lo:
                into[key] += (hi - lo) / 1e9 / scale
                at = hi
            k += 1
        if p1 > at:
            rest.append((at, p1))
    return rest


def _host_spans(data, span_prefix):
    """{line: [(name, start, end, event)]} of the program's and the
    harness's spans on every host line."""
    lines = {}
    for p in data.planes:
        if DEVICE_PLANE.match(p.name):
            continue
        for n, line in enumerate(p.lines):
            got = []
            for ev in line.events:
                name = ev.name
                if name.startswith(PROGRAM_PREFIX) or \
                        name.startswith(span_prefix):
                    s = int(ev.start_ns)
                    got.append((name, s, s + int(ev.duration_ns), ev))
            if got:
                lines[f"{p.name}/{n}:{line.name}"] = got
    return lines


def _keyed(spans, span_prefix):
    """The stepping line's spans as (start, end, key): a program span
    inside a step takes the step's ``action``."""
    steps = sorted((s, e, dict(ev.stats).get("action"))
                   for name, s, e, ev in spans if name in STEP_SPANS)
    starts = [s for s, _, _ in steps]
    out = []
    for name, s, e, _ev in spans:
        if name == span_prefix + WINDOW_SPAN:
            continue
        key = name
        if name.startswith(PROGRAM_PREFIX):
            i = bisect_right(starts, s) - 1
            if i >= 0 and s < steps[i][1] and steps[i][2] is not None:
                key = f"{name}[{steps[i][2]}]"
        out.append((s, e, key))
    return out, steps


def split(data, n_devices=1, span_prefix="bench."):
    """The trace (what ``ProfileData.from_file`` gives, or anything shaped
    like it) to an ``IdleSplit``; ``span_prefix`` is the harness's."""
    devices = sorted((int(DEVICE_PLANE.match(p.name).group(1)), p)
                     for p in data.planes if DEVICE_PLANE.match(p.name))
    if not devices:
        raise ValueError("the trace holds no device plane (/device:TPU:n)")
    lines = _host_spans(data, span_prefix)
    window = span_prefix + WINDOW_SPAN
    wins = [(s, e) for spans in lines.values()
            for name, s, e, _ in spans if name == window]
    if not wins:
        raise ValueError(f"the trace holds no {window} span")
    lo, hi = min(s for s, _ in wins), max(e for _, e in wins)
    out = IdleSplit()
    out.window_s = (hi - lo) / 1e9

    n_steps = {ln: sum(1 for name, *_ in spans if name in STEP_SPANS)
               for ln, spans in lines.items()}
    stepping = max(n_steps, key=n_steps.get, default=None)
    if stepping is not None and not n_steps[stepping]:
        stepping = None
    own, others = [], []
    if stepping is not None:
        out.stepping_line = stepping
        own, steps = _keyed(lines[stepping], span_prefix)
        out.steps.update(a for _, e, a in steps if lo < e <= hi)
        out.spans_in_window = sum(
            1 for name, s, _, _ in lines[stepping]
            if name.startswith(PROGRAM_PREFIX) and lo <= s < hi)
    for ln, spans in lines.items():
        if ln != stepping:
            others += [(s, e, name) for name, s, e, _ in spans
                       if name.startswith(span_prefix) and name != window]
    own, others = innermost(own), innermost(others)

    used = devices[:n_devices]
    for _idx, plane in used:
        ops, mods = [], []
        for line in plane.lines:
            if line.name in (OPS_LINE, MODULES_LINE):
                ivals = [(int(ev.start_ns),
                          int(ev.start_ns) + int(ev.duration_ns))
                         for ev in line.events]
                (ops if line.name == OPS_LINE else mods).extend(ivals)
        busy = clip(union(ops), lo, hi)
        out.busy_s += total(busy) / 1e9 / len(used)
        pieces = gaps(busy, lo, hi)
        running = [(s, e, IN_PROGRAM) for s, e in clip(union(mods), lo, hi)]
        for segments in (running, own, others):
            pieces = assign(pieces, segments, out.idle_by_span, len(used))
        if pieces:
            out.idle_by_span[UNATTRIBUTED] += total(pieces) / 1e9 / len(used)
    return out
