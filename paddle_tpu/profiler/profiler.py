"""Profiler: spans, scheduler windows, chrome-trace export.

TPU-native equivalent of the reference's profiler (reference:
python/paddle/profiler/profiler.py — ``Profiler`` with states
``profiler.py:79``, window scheduler ``make_scheduler``, chrome trace
``export_chrome_tracing:215``; C++ host tracer
platform/profiler/host_tracer.cc RecordEvent spans). Two layers:

- host spans: ``RecordEvent`` context managers, written into the
  ``jax.profiler`` trace (on the device planes' clock) whenever a
  session is active and collected for the chrome-trace JSON export the
  reference emits;
- device trace: ``jax.profiler`` start/stop around the profiled window
  (XLA's own profiler session → TensorBoard/XPlane dump directory);
- runtime counters: the process-wide ``profiler.stats`` registry
  (per-op dispatch counts, VJP-cache hits, compile histograms, pool
  gauges) is sampled at start/step/stop into chrome-trace counter
  events (``"ph": "C"``) and folded into ``summary()``.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from enum import Enum
from typing import Callable, List, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation

__all__ = ["Profiler", "ProfilerState", "ProfilerTarget", "RecordEvent",
           "SPAN_PREFIX", "make_scheduler", "export_chrome_tracing",
           "load_profiler_result", "dump_rank",
           "start_span_capture", "stop_span_capture"]


def _process_index() -> int:
    """Rank of this process (0 when jax is uninitialized): stamps trace
    metadata, worker names, and fleet snapshots so multi-host runs stay
    distinguishable after merging."""
    try:
        import jax

        return int(jax.process_index())
    except Exception:
        return 0


class ProfilerState(Enum):
    """(profiler.py:79)"""
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(Enum):
    """(profiler.py:99) — CPU=host spans, GPU→TPU device trace."""
    CPU = 0
    GPU = 1
    CUSTOM_DEVICE = 2
    TPU = 3


class _SpanStore(threading.local):
    def __init__(self):
        self.events: List[dict] = []
        self.enabled = False


_SPANS = _SpanStore()

# Cross-thread span sinks: ``_SPANS`` is thread-local by design (the
# Profiler lifecycle owns the calling thread's spans), which silently
# drops RecordEvent spans emitted from BACKGROUND threads — the async
# migration streamer, replica step threads. ``start_span_capture``
# registers a process-wide sink every thread's ``RecordEvent.end``
# appends into, so a trace test (or a fleet timeline) can observe
# concurrent spans from all threads with wall-clock-comparable ``ts``.
_SINK_LOCK = threading.Lock()
_SINKS: List[List[dict]] = []


def start_span_capture() -> List[dict]:
    """Begin capturing RecordEvent spans from ALL threads into the
    returned list (chrome-trace "X" dicts, appended live). Sinks stack:
    each capture sees every span ended while it is registered."""
    sink: List[dict] = []
    with _SINK_LOCK:
        _SINKS.append(sink)
    return sink


def stop_span_capture(sink: List[dict]) -> List[dict]:
    """Unregister a ``start_span_capture`` sink and return it."""
    with _SINK_LOCK:
        try:
            _SINKS.remove(sink)
        except ValueError:
            pass
    return sink


#: prefix of every program span in the profiler's trace, so that ONE
#: prefix picks the program's spans out of a trace that also holds a
#: harness's own (the benchmark's are ``bench.``)
SPAN_PREFIX = "pt."


class RecordEvent:
    """The program's one span (reference RecordEvent, event_tracing.h):
    context manager / begin-end pair.

    It lands in two places. Under ANY active ``jax.profiler`` session it
    is a ``TraceAnnotation`` named ``SPAN_PREFIX + name`` in the
    ``.xplane.pb``, on the clock of the device planes, with ``ids``
    (``step=``, ``rid=``, ``program=``) as its arguments; ``step_num=``
    makes it a ``StepTraceAnnotation`` (the profiler's own step
    marker). And while a ``Profiler`` window or a
    ``start_span_capture`` sink is open it is appended there as a
    chrome-trace "X" event under its bare name. With neither it costs
    two clock reads and one no-op ``TraceMe``; nothing is appended.
    ``dur_ms`` is the span's own length, for a histogram that must
    agree with the span."""

    __slots__ = ("name", "_ids", "_t0", "_t1", "_ann")

    def __init__(self, name: str, event_type=None, **ids):
        self.name = name
        self._ids = ids
        self._t0 = self._t1 = self._ann = None

    def begin(self):
        cls = StepTraceAnnotation if "step_num" in self._ids \
            else TraceAnnotation
        self._ann = cls(SPAN_PREFIX + self.name, **self._ids)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()

    def end(self):
        if self._t0 is None:
            return
        t1 = self._t1 = time.perf_counter_ns()
        self._ann.__exit__(None, None, None)
        self._ann = None
        if not (_SPANS.enabled or _SINKS):
            return
        ev = {
            "name": self.name, "ph": "X", "pid": os.getpid(),
            "tid": threading.get_ident() % 2 ** 31,
            "ts": self._t0 / 1e3, "dur": (t1 - self._t0) / 1e3,
            "cat": "host",
        }
        if self._ids:
            ev["args"] = dict(self._ids)
        if _SPANS.enabled:
            _SPANS.events.append(ev)
        if _SINKS:
            with _SINK_LOCK:
                for s in _SINKS:
                    s.append(ev)

    def annotate(self, **ids):
        """Identifiers that are known only once the span is open (the
        action a step picked)."""
        self._ids.update(ids)
        self._ann.set_metadata(**ids)

    @property
    def dur_ms(self) -> float:
        """Milliseconds between ``begin()`` and ``end()``."""
        return (self._t1 - self._t0) / 1e6

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def make_scheduler(*, closed: int, ready: int, record: int,
                   repeat: int = 0, skip_first: int = 0) -> Callable:
    """(profiler.py make_scheduler): step → ProfilerState window fn."""
    period = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def _default_on_trace_ready(prof: "Profiler"):
    d = prof.log_dir or "./profiler_log"
    os.makedirs(d, exist_ok=True)
    prof.export(os.path.join(
        d, f"paddle_tpu_trace_{int(time.time())}.json"))


class Profiler:
    """(profiler.py Profiler parity)."""

    def __init__(self, *, targets=None, scheduler=None,
                 on_trace_ready=None, timer_only: bool = False,
                 log_dir: Optional[str] = None):
        self.targets = list(targets or [ProfilerTarget.CPU])
        if isinstance(scheduler, (tuple, list)):
            lo, hi = scheduler
            scheduler = make_scheduler(closed=lo, ready=0, record=hi - lo,
                                       repeat=1)
        self.scheduler = scheduler
        self.on_trace_ready = on_trace_ready or _default_on_trace_ready
        self.timer_only = timer_only
        self.log_dir = log_dir
        self.step_num = 0
        self.state = ProfilerState.CLOSED
        self._events: List[dict] = []
        self._device_active = False
        from .timer import Benchmark

        self.benchmark = Benchmark()

    # ---- device (XLA) session ----
    def _device_start(self):
        if self.timer_only or self._device_active:
            return
        want_device = any(t in (ProfilerTarget.GPU, ProfilerTarget.TPU,
                                ProfilerTarget.CUSTOM_DEVICE)
                          for t in self.targets)
        if not want_device:
            return
        try:
            import jax.profiler

            d = self.log_dir or "./profiler_log"
            os.makedirs(d, exist_ok=True)
            jax.profiler.start_trace(d)
            self._device_active = True
        except Exception:
            self._device_active = False

    def _device_stop(self):
        if self._device_active:
            try:
                import jax.profiler

                jax.profiler.stop_trace()
            except Exception:
                pass
            self._device_active = False

    # ---- runtime-counter sampling (profiler.stats -> "ph": "C") ----
    def _sample_counters(self):
        """One chrome-trace counter event per live stats metric — the
        counter timeline interleaves with the "X" spans in the same
        exported file (the reference emits device counters the same
        way through its chrome-trace serializer). HBM telemetry is
        refreshed first so the ``hbm.*`` gauges ride the same timeline
        (memory sampled at step boundaries, reference memory view)."""
        from . import memory, stats

        try:
            memory.sample()
        except Exception:
            pass
        snap = stats.snapshot()
        ts = time.perf_counter_ns() / 1e3
        pid = os.getpid()
        for name, val in {**snap["counters"], **snap["gauges"]}.items():
            self._events.append({
                "name": name, "ph": "C", "pid": pid, "tid": 0,
                "ts": ts, "cat": "counter", "args": {"value": val},
            })

    # ---- lifecycle ----
    def start(self):
        self.benchmark.begin()
        _SPANS.enabled = True
        _SPANS.events = []
        self.state = self.scheduler(self.step_num) if self.scheduler \
            else ProfilerState.RECORD
        if self.state in (ProfilerState.RECORD,
                          ProfilerState.RECORD_AND_RETURN):
            self._device_start()
        self._sample_counters()
        return self

    def stop(self):
        self._device_stop()
        _SPANS.enabled = False
        self._events.extend(_SPANS.events)
        _SPANS.events = []
        self._sample_counters()
        self.state = ProfilerState.CLOSED
        if self.on_trace_ready:
            self.on_trace_ready(self)

    def step(self, num_samples: int = 1, sync_value=None):
        self.benchmark.step(num_samples, sync_value=sync_value)
        self._events.extend(_SPANS.events)
        _SPANS.events = []
        self._sample_counters()
        self.step_num += 1
        if self.scheduler is None:
            return
        new = self.scheduler(self.step_num)
        if new in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN) \
                and self.state not in (ProfilerState.RECORD,
                                       ProfilerState.RECORD_AND_RETURN):
            self._device_start()
        if new == ProfilerState.CLOSED and self._device_active:
            self._device_stop()
        self.state = new

    def step_info(self, unit: str = "samples") -> str:
        return self.benchmark.step_info(unit)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # ---- export ----
    def export(self, path: str, format: str = "json"):
        """(export_chrome_tracing:215): chrome-trace JSON. The
        ``metadata`` block stamps the producing rank/pid so
        tools/trace_merge.py can fold per-rank traces into one
        fleet timeline without relying on filenames."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump({"traceEvents": self._events,
                       "displayTimeUnit": "ms",
                       "metadata": {"process_index": _process_index(),
                                    "pid": os.getpid()}}, f)
        return path

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        """Aggregate span table (profiler_statistic.py parity): per-name
        count / total / avg / max over the recorded "X" spans (the auto
        ``op::`` dispatch spans give per-op call counts for free), plus
        a cache section reading the stats registry (VJP-cache hit rate,
        jit tracings) — the counters that distinguish a retrace storm
        from steady cache hits. Returns ``{name: [total_ms, calls]}``."""
        agg = {}
        maxes = {}
        for e in self._events:
            if e.get("ph") != "X":
                continue
            a = agg.setdefault(e["name"], [0.0, 0])
            a[0] += e["dur"] / 1e3
            a[1] += 1
            maxes[e["name"]] = max(maxes.get(e["name"], 0.0),
                                   e["dur"] / 1e3)
        lines = [f"{'Name':<40}{'Calls':>8}{'Total(ms)':>12}"
                 f"{'Avg(ms)':>12}{'Max(ms)':>12}"]
        for name, (tot, cnt) in sorted(agg.items(), key=lambda x: -x[1][0]):
            lines.append(f"{name:<40}{cnt:>8}{tot:>12.3f}"
                         f"{tot / cnt:>12.3f}{maxes[name]:>12.3f}")
        from . import stats

        hit_rate = stats.vjp_cache_hit_rate()
        cache_lines = ["", f"{'Cache / compile counters':<40}"]
        if hit_rate is not None:
            cache_lines.append(
                f"{'vjp_cache hit rate':<40}"
                f"{100 * hit_rate:>11.1f}%"
                f"  (hit={stats.counter('vjp_cache.hit').value}"
                f" miss={stats.counter('vjp_cache.miss').value}"
                f" admit={stats.counter('vjp_cache.admit').value}"
                f" blocklisted="
                f"{stats.counter('vjp_cache.blocklisted').value})")
        for cname in ("jit.trace", "jit.cache_hit"):
            v = stats.counter(cname).value
            if v:
                cache_lines.append(f"{cname:<40}{v:>8}")
        for hname in ("compile.vjp_trace_us", "compile.vjp_build_us"):
            h = stats.histogram(hname)
            if h.count:
                cache_lines.append(
                    f"{hname:<40}{h.count:>8}{h.total / 1e3:>12.3f}"
                    f"{h.avg / 1e3:>12.3f}{(h.max or 0) / 1e3:>12.3f}")
        extra_lines = self._roofline_lines() + self._hbm_lines()
        out = "\n".join(lines + (cache_lines
                                 if len(cache_lines) > 2 else [])
                        + extra_lines)
        print(out)
        return agg

    @staticmethod
    def _roofline_lines():
        """Per-program cost-model roofline section (programs recorded by
        the jit layers via profiler.roofline)."""
        from . import roofline

        text = roofline.format_report()
        if not text:
            return []
        return ["", f"{'Roofline (XLA cost model)':<40}"] + text.split("\n")

    @staticmethod
    def _hbm_lines():
        """HBM peak-watermark section: allocator peak vs limit (PJRT),
        or the live-buffer census on backends without counters."""
        from . import memory

        try:
            wm = memory.watermark()
        except Exception:
            wm = None
        if not wm:
            return []
        lines = ["", f"{'HBM memory watermark':<40}"]
        if wm["source"] == "pjrt":
            pct = wm.get("peak_pct_of_limit")
            lines.append(
                f"{'peak_bytes_in_use':<40}"
                f"{wm['peak_bytes_in_use'] / 2**30:>11.3f}GiB"
                + (f"  ({pct:.1f}% of limit)" if pct is not None else ""))
            lines.append(f"{'bytes_in_use':<40}"
                         f"{wm['bytes_in_use'] / 2**30:>11.3f}GiB")
            if wm.get("bytes_limit"):
                lines.append(f"{'bytes_limit':<40}"
                             f"{wm['bytes_limit'] / 2**30:>11.3f}GiB")
        else:
            lines.append(f"{'live buffers':<40}{wm['live_buffers']:>8}"
                         f"{wm['bytes_in_use'] / 2**20:>12.3f}MiB")
            for s in wm.get("top_shapes", [])[:3]:
                lines.append(f"  {s['shape']:<38}{s['count']:>8}"
                             f"{s['bytes'] / 2**20:>12.3f}MiB")
        return lines


def export_chrome_tracing(dir_name: str, worker_name: str = None):
    """(profiler.py export_chrome_tracing:215): returns an
    on_trace_ready callback writing into ``dir_name``.

    The default worker name includes ``jax.process_index()`` — a plain
    ``host_{pid}`` collides when two hosts of a multi-host run land the
    same pid and write into a shared run dir."""
    def handler(prof: Profiler):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"rank{_process_index()}_host_{os.getpid()}"
        prof.export(os.path.join(
            dir_name, f"{name}_time_{int(time.time())}"
                      f".paddle_trace.json"))

    return handler


def dump_rank(run_dir: str, profiler: "Profiler" = None) -> dict:
    """Write THIS rank's observability artifacts into a shared run dir:

    - ``stats_rank{i}.json`` — ``stats.snapshot()`` (rank-stamped meta)
      with a fresh HBM sample folded in first;
    - ``trace_rank{i}.json`` — the given profiler's chrome trace, when
      one is passed.

    Every rank of a multiproc run calls this with the SAME ``run_dir``
    (each writes only its own files — no cross-rank coordination), then
    ``tools/trace_merge.py RUN_DIR`` folds the rank files into one
    merged trace + one fleet stats snapshot. Returns the paths written.
    """
    from . import memory, stats

    os.makedirs(run_dir, exist_ok=True)
    rank = _process_index()
    try:
        memory.sample()
    except Exception:
        pass
    out = {}
    stats_path = os.path.join(run_dir, f"stats_rank{rank}.json")
    with open(stats_path, "w") as f:
        json.dump(stats.snapshot(), f)
    out["stats"] = stats_path
    if profiler is not None:
        out["trace"] = profiler.export(
            os.path.join(run_dir, f"trace_rank{rank}.json"))
    return out


def load_profiler_result(filename: str):
    with open(filename) as f:
        return json.load(f)
