"""Process-wide runtime metrics registry: counters, gauges, histograms.

TPU-native equivalent of the reference's per-op statistic tables
(reference: python/paddle/profiler/profiler_statistic.py aggregating the
host tracer's RecordEvent stream, plus the op-count tables the C++
HostTraceLevel machinery feeds). Where the reference derives counts from
the trace, this registry is written DIRECTLY by the hot layers — eager
dispatch (per-op call counts, VJP-cache hit/miss), the autograd engine
(sweeps, nodes), jit compile caches (tracings vs hits), the inference
engine (pool pages, decode steps) and the collectives (op counts,
bytes) — so telemetry exists even when no profiler window is open.

Design constraints:

- near-zero cost when disabled: every mutation checks one module-level
  bool before touching the metric (`disable()` turns the whole registry
  into no-ops);
- thread-safe: each metric guards its state with one lock (metrics are
  updated from dispatch on any thread; snapshot() sees consistent
  values);
- JSON-able: ``snapshot()`` returns plain dicts so bench entry points
  (bench.py, tools/op_bench.py) can embed telemetry into BENCH_*.json,
  and the profiler can emit chrome-trace counter events ("ph": "C")
  from the same source.

Conventions for the built-in instrumentation (all optional reading):

- ``op.<name>``                per-op eager dispatch call counters
- ``vjp_cache.{hit,miss,admit,blocklisted,uncacheable}``  taped-VJP
  trace cache outcomes (ops/dispatch.py)
- ``fwd_cache.{hit,miss,admit,blocklisted,blocked,uncacheable}``
  compiled-forward no-grad fast-path outcomes (ops/dispatch.py)
- ``compile.{vjp_trace_us,vjp_build_us}``   histograms of uncached
  jax.vjp trace time / cache-entry build time
- ``compile.fwd_trace_us``     histogram of compiled-forward admission
  trace+compile time
- ``jit.{trace,cache_hit}``    to_static program-cache outcomes
- ``autograd.{sweeps,nodes}``  run_backward sweeps and executed nodes
- ``inference.*`` / ``serving.*``  pool sizes, decode steps, admission
  (``serving.admission_skips`` skip-ahead pass-overs,
  ``serving.prefix_{hit,miss,pages_saved}`` prefix/KV reuse,
  ``serving.wasted_decode_tokens`` chunk tail work past req.done)
- ``serve.*``                  per-request SLO telemetry of the serving
  frontend (paddle_tpu/serving): ``serve.{ttft_ms,tpot_ms,
  request_tpot_ms,queue_wait_ms}`` histograms plus
  ``serve.{submitted,prefill_chunks,prefill_tokens}`` counters
  (``serving.unserved`` stamps requests still waiting when run()
  exits — the ones queue-wait histograms never saw)
- ``journal.{events,dropped}`` serving flight-recorder ring gauges
  (serving/journal.py: events ever recorded / overwritten by wrap)
- ``slo.*``                    SLO monitor (serving/slo.py):
  ``slo.goodput`` rolling fraction of finished requests meeting both
  TTFT and TPOT targets, ``slo.burn_rate`` error-budget burn,
  ``slo.{finished,ok,ttft_miss,tpot_miss}`` counters and
  ``slo.{queue_depth,slot_occupancy}`` load gauges
- ``spec.*``                   speculative decoding
  (inference/speculative.py): ``spec.k`` / ``spec.draft_params``
  gauges and ``spec.{propose_ms,verify_ms}`` timing histograms; the
  round/token accounting lives in
  ``serving.spec_{rounds,drafted_tokens,accepted_tokens,
  rejected_tokens}`` and the ``serve.accept_len`` histogram
- ``quant.{act_quant_calls,a8w8_matmuls}``  executed dynamic
  activation-quant ops / int8 x int8 serving matmuls (A8W8 decode,
  QuantedLinear(a8w8=True)) — counted at the dispatch layer, since
  inside a traced program the quant body runs once per compile
- ``moe.dropped_tokens``       token->expert assignments discarded by
  the MoE capacity bound (incubate/moe/moe_layer.py _gshard_dispatch)
  — counted on the eager forward path only (data-dependent)
- ``lint.{findings,waived}``   tpu_lint results (unwaivered / waived
  finding counts) published by every suite run — the CLI
  (tools/tpu_lint.py) and the bench/profiling preflight gate
  (analysis/preflight.py) — so bench telemetry records the lint state
  its numbers were measured under and bench_gate can ratchet on it
- ``dist.<op>.{calls,bytes}``  collective op counts and payload bytes
- ``fleet.*``                  multi-replica serving router
  (serving/router.py): ``fleet.{replicas,replicas_alive,
  circuit_open}`` gauges and ``fleet.{dispatches,failovers,
  failover_requests,migrations,migrated_pages,hedges,shed}``
  counters — the front-tier health/failover/drain accounting
  tools/serve_top.py --fleet renders — plus the tiered-KV /
  disaggregation accounting: ``fleet.{spills,restores,spill_bytes,
  restore_bytes,host_evictions}`` host-tier page traffic
  (serving/host_tier.py), ``fleet.{handoffs,handoff_pages}``
  prefill→decode slot handoffs, and
  ``fleet.directory_{hits,pulls,misses}`` prefix-directory routing
  verdicts
- ``tier.*``                   host-DRAM KV tier occupancy gauges
  (serving/host_tier.py): ``tier.host_{pages,bytes,
  capacity_bytes}``, summed over every engine's tier in the
  process — the serve_top fleet tier view's source
- ``roofline.*``               MFU / BW utilization vs device peaks
  (profiler/roofline.py; the achieved rates are in its table)
- ``hbm.*``                    device memory telemetry
  (profiler/memory.py)
- ``serve.step.*_ms``          per-step serving-time ATTRIBUTION
  (serving/scheduler.py ``_observe_step``): each scheduler step's
  wall time split into ``serve.step.{admit,prefill_chunk,
  decode_chunk,spec_verify,migration,host_overhead,total}_ms``
  histograms on the injectable serving clock — the phase sums equal
  the step wall time (host_overhead is the residual), so "where did
  the step go" is answerable from telemetry alone; the work phase
  once more as ``serve.step.{plan,run,emit}_ms`` (``run`` is the
  program call until its token fetch returned)
- ``jit.train_step.*_ms``      host phases of one ``TrainStep``
  call (jit/train_step.py): ``args``, ``dispatch``, ``rebind``, from
  the stamps of the ``pt.train.*`` spans
- ``telemetry.*``              the continuous time-series sampler's
  own accounting (profiler/timeseries.py):
  ``telemetry.ticks`` sampler passes and ``telemetry.tick_us`` the
  measured per-tick overhead histogram
- ``alert.*``                  the alert rule engine
  (profiler/alerts.py): ``alert.{fired,resolved}`` lifecycle
  counters and the ``alert.active`` gauge
- ``usage.*``                  the per-request usage ledger's own
  accounting (serving/accounting.py): ``usage.records`` closed
  usage records
- ``tenant.*``                 BOUNDED per-tenant rollup gauges
  (serving/accounting.py + serving/slo.py):
  ``tenant.{count,max_share,min_goodput}`` and the index-keyed
  ``tenant.top<i>.device_ms`` top-K slice — never one key per
  tenant; names live in the usage JSONL, not the registry
- ``lora.*``                   batched multi-LoRA serving
  (serving/adapters.py + nn/functional/lora.py):
  ``lora.grouped_launches`` ragged delta-GEMM dispatches (one per
  adaptered chunk; each covers every target projection via the
  traced work map), ``lora.swaps`` hot load/unload events against
  the AdapterBank, and the ``lora.active_adapters`` gauge (loaded,
  non-draining adapter slots)
- ``t.*``                      scratch namespace reserved for tests

Every metric the framework registers MUST use one of these prefixes
(``CONVENTION_PREFIXES``) — tests/test_profiler_stats.py lints the live
registry against it, so fleet aggregation (tools/trace_merge.py) and
the bench gate (tools/bench_gate.py) can rely on stable names.
"""
from __future__ import annotations

import math
import random
import threading
import time
from typing import Dict, Optional

__all__ = [
    "Counter", "Gauge", "Histogram", "counter", "gauge", "histogram",
    "inc", "set_gauge", "observe", "snapshot", "reset", "enable",
    "disable", "is_enabled", "timed", "sample_values",
    "CONVENTION_PREFIXES",
]

#: documented metric-name namespaces (see module docstring / README
#: conventions table); the naming lint asserts every registered metric
#: starts with one of these
CONVENTION_PREFIXES = (
    "op.", "vjp_cache.", "fwd_cache.", "compile.", "jit.", "autograd.",
    "inference.", "serving.", "serve.", "journal.", "slo.", "spec.",
    "quant.", "moe.", "dist.", "fleet.", "tier.", "roofline.", "hbm.",
    "lint.", "telemetry.", "alert.", "usage.", "tenant.", "lora.",
    "t.",
)

_ENABLED = True
_REGISTRY_LOCK = threading.Lock()
_COUNTERS: Dict[str, "Counter"] = {}
_GAUGES: Dict[str, "Gauge"] = {}
_HISTOGRAMS: Dict[str, "Histogram"] = {}


def enable() -> None:
    global _ENABLED
    _ENABLED = True


def disable() -> None:
    global _ENABLED
    _ENABLED = False


def is_enabled() -> bool:
    return _ENABLED


class Counter:
    """Monotonic event counter."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._value = 0
        self._lock = threading.Lock()

    def inc(self, n: int = 1) -> None:
        if not _ENABLED:
            return
        with self._lock:
            self._value += n

    @property
    def value(self) -> int:
        return self._value

    def _reset(self) -> None:
        with self._lock:
            self._value = 0


class Gauge:
    """Last-written instantaneous value (pool pages in use, ...)."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._value = 0.0
        self._lock = threading.Lock()

    def set(self, v) -> None:
        if not _ENABLED:
            return
        with self._lock:
            self._value = float(v)

    def inc(self, n=1) -> None:
        if not _ENABLED:
            return
        with self._lock:
            self._value += n

    def dec(self, n=1) -> None:
        self.inc(-n)

    @property
    def value(self) -> float:
        return self._value

    def _reset(self) -> None:
        with self._lock:
            self._value = 0.0


class Histogram:
    """Streaming distribution summary: count/total/min/max, powers-of-2
    buckets, and a bounded RESERVOIR of raw samples.

    The buckets tell a retrace storm (many large observations) from
    steady cache hits and stay exported for chrome-trace counters and
    cross-rank folding (tools/trace_merge.py folds summaries bucket-
    by-bucket). The reservoir fixes their percentile problem: bucket-
    midpoint estimates are off by up to 2x for small-count histograms
    (a 7-request serve bench's p99 TTFT landed on a power-of-2 edge,
    not a real observation). Up to ``RESERVOIR_SIZE`` samples are kept
    verbatim — percentiles are EXACT until the 4097th observation —
    then Vitter's Algorithm R keeps a uniform sample, driven by a
    per-instance seeded RNG so eviction (and thus every snapshot) is
    deterministic for a given observation sequence."""

    __slots__ = ("name", "count", "total", "min", "max", "_buckets",
                 "_samples", "_rng", "_lock")

    #: bucket upper bounds double from 1; observations are expected in
    #: microseconds for the compile/wall-time histograms
    N_BUCKETS = 32
    #: reservoir capacity: exact percentiles up to this many samples,
    #: deterministic uniform sampling beyond (0 disables, falling back
    #: to the bucket estimator)
    RESERVOIR_SIZE = 4096

    def __init__(self, name: str):
        self.name = name
        self.count = 0
        self.total = 0.0
        self.min = None
        self.max = None
        self._buckets = [0] * self.N_BUCKETS
        self._samples: list = []
        self._rng = random.Random(0x5EED)
        self._lock = threading.Lock()

    def observe(self, v) -> None:
        if not _ENABLED:
            return
        v = float(v)
        with self._lock:
            self.count += 1
            self.total += v
            self.min = v if self.min is None else min(self.min, v)
            self.max = v if self.max is None else max(self.max, v)
            b = 0
            edge = 1.0
            while v > edge and b < self.N_BUCKETS - 1:
                edge *= 2.0
                b += 1
            self._buckets[b] += 1
            if len(self._samples) < self.RESERVOIR_SIZE:
                self._samples.append(v)
            else:
                # Algorithm R: the i-th observation (count = i+1)
                # replaces a uniformly random reservoir slot with
                # probability RESERVOIR_SIZE / count
                j = self._rng.randrange(self.count)
                if j < self.RESERVOIR_SIZE:
                    self._samples[j] = v

    @property
    def avg(self) -> float:
        return self.total / self.count if self.count else 0.0

    @staticmethod
    def _quantile_sorted(s, q: float):
        """Empirical q-quantile of a sorted sample (the ceil(qN)-th
        order statistic — an OBSERVED value, never an interpolation)."""
        idx = min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))
        return round(s[idx], 3)

    def _bucket_percentile_locked(self, q: float):
        """Bucket-derived percentile estimate (linear interpolation
        within the winning power-of-2 bucket, clamped to the exact
        min/max) — the pre-reservoir fallback, only reached when
        RESERVOIR_SIZE is 0. Callers hold self._lock."""
        if not self.count:
            return None
        target = q * self.count
        cum = 0
        for b, n in enumerate(self._buckets):
            if not n:
                continue
            prev, cum = cum, cum + n
            if cum >= target:
                lo = 0.0 if b == 0 else 2.0 ** (b - 1)
                hi = 2.0 ** b
                est = lo + (hi - lo) * (target - prev) / n
                lo_clamp = self.min if self.min is not None else est
                hi_clamp = self.max if self.max is not None else est
                return round(min(max(est, lo_clamp), hi_clamp), 3)
        return self.max

    def _percentile_locked(self, q: float):
        if not self.count:
            return None
        if self._samples:
            return self._quantile_sorted(sorted(self._samples), q)
        return self._bucket_percentile_locked(q)

    def percentile(self, q: float):
        """q-quantile (q in [0, 1]): exact while the reservoir covers
        every observation, reservoir-sampled beyond; None before any
        observation."""
        with self._lock:
            return self._percentile_locked(q)

    def summary(self) -> dict:
        with self._lock:
            # buckets as [upper_edge, count] pairs (nonzero only) so the
            # retrace-storm-vs-steady-hits shape survives into snapshots
            # and can be re-folded across ranks (tools/trace_merge.py)
            buckets = [[(1.0 if b == 0 else 2.0 ** b), n]
                       for b, n in enumerate(self._buckets) if n]
            if self._samples:
                s = sorted(self._samples)
                p50, p90, p99 = (self._quantile_sorted(s, q)
                                 for q in (0.50, 0.90, 0.99))
            else:
                p50, p90, p99 = (self._bucket_percentile_locked(q)
                                 for q in (0.50, 0.90, 0.99))
            return {
                "count": self.count,
                "total": round(self.total, 3),
                "avg": round(self.avg, 3),
                "min": self.min,
                "max": self.max,
                "p50": p50,
                "p90": p90,
                "p99": p99,
                "buckets": buckets,
            }

    def _reset(self) -> None:
        with self._lock:
            self.count = 0
            self.total = 0.0
            self.min = None
            self.max = None
            self._buckets = [0] * self.N_BUCKETS
            self._samples = []
            self._rng = random.Random(0x5EED)


def counter(name: str) -> Counter:
    c = _COUNTERS.get(name)
    if c is None:
        with _REGISTRY_LOCK:
            c = _COUNTERS.setdefault(name, Counter(name))
    return c


def gauge(name: str) -> Gauge:
    g = _GAUGES.get(name)
    if g is None:
        with _REGISTRY_LOCK:
            g = _GAUGES.setdefault(name, Gauge(name))
    return g


def histogram(name: str) -> Histogram:
    h = _HISTOGRAMS.get(name)
    if h is None:
        with _REGISTRY_LOCK:
            h = _HISTOGRAMS.setdefault(name, Histogram(name))
    return h


def inc(name: str, n: int = 1) -> None:
    if _ENABLED:
        counter(name).inc(n)


def set_gauge(name: str, v) -> None:
    if _ENABLED:
        gauge(name).set(v)


def observe(name: str, v) -> None:
    if _ENABLED:
        histogram(name).observe(v)


class timed:
    """Context manager observing its wall time (µs) into a histogram,
    and counting into an optional companion counter::

        with stats.timed("compile.vjp_trace_us"):
            ...  # traced work
    """

    __slots__ = ("_name", "_t0")

    def __init__(self, name: str):
        self._name = name
        self._t0 = None

    def __enter__(self):
        if _ENABLED:
            self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        if self._t0 is not None and _ENABLED:
            observe(self._name,
                    (time.perf_counter_ns() - self._t0) / 1e3)
        return False


def _process_meta() -> dict:
    """Rank stamp for multi-host aggregation: which process produced
    this snapshot (tools/trace_merge.py folds per-rank snapshots into
    one fleet view keyed on this)."""
    pi, pc = 0, 1
    try:
        import jax

        pi, pc = jax.process_index(), jax.process_count()
    except Exception:
        pass
    import os

    return {"process_index": int(pi), "process_count": int(pc),
            "pid": os.getpid()}


def _registered():
    """Consistent copy of the registry's metric lists. Taken under
    ``_REGISTRY_LOCK`` so a snapshot/reset pass racing a writer thread
    that is REGISTERING new names (the time-series sampler hammer
    case) never iterates a mutating dict; per-metric values stay
    guarded by each metric's own lock."""
    with _REGISTRY_LOCK:
        return (sorted(_COUNTERS.items()), sorted(_GAUGES.items()),
                sorted(_HISTOGRAMS.items()))


def snapshot(prefix: Optional[str] = None) -> dict:
    """JSON-able view of every metric (optionally name-prefixed):
    ``{"meta": {...}, "counters": {...}, "gauges": {...},
    "histograms": {...}}`` — ``meta`` stamps the producing rank.
    Safe against concurrent writers/registrations: the name set is
    copied under the registry lock and each histogram summary is read
    under its own lock (no torn count/bucket pairs)."""
    def keep(name):
        return prefix is None or name.startswith(prefix)

    counters, gauges, hists = _registered()
    return {
        "meta": _process_meta(),
        "counters": {n: c.value for n, c in counters
                     if keep(n) and c.value},
        "gauges": {n: g.value for n, g in gauges if keep(n)},
        "histograms": {n: h.summary() for n, h in hists
                       if keep(n) and h.count},
    }


def sample_values(prefix: Optional[str] = None):
    """One lock-cheap telemetry pass (the time-series sampler's tick
    source — profiler/timeseries.py): ``(counters, gauges,
    histograms)`` plain dicts, where histograms carry only the
    ``(count, total)`` pair read under the histogram lock — no
    reservoir sort, no bucket list build, so a tick over hundreds of
    metrics stays microseconds."""
    def keep(name):
        return prefix is None or name.startswith(prefix)

    counters, gauges, hists = _registered()
    hv = {}
    for n, h in hists:
        if not keep(n):
            continue
        with h._lock:
            if h.count:
                hv[n] = (h.count, h.total)
    return ({n: c.value for n, c in counters if keep(n) and c.value},
            {n: g.value for n, g in gauges if keep(n)},
            hv)


def reset() -> None:
    """Zero every metric (keeps the registry's objects alive — cached
    references in hot paths stay valid, and every registered series
    DEFINITION survives: a concurrent sampler keeps reading the same
    metric objects, now zeroed)."""
    counters, gauges, hists = _registered()
    for _, c in counters:
        c._reset()
    for _, g in gauges:
        g._reset()
    for _, h in hists:
        h._reset()


def vjp_cache_hit_rate() -> Optional[float]:
    """hit / (hit + miss) over the taped-VJP trace cache, or None before
    any taped dispatch ran."""
    hit = counter("vjp_cache.hit").value
    miss = counter("vjp_cache.miss").value
    return hit / (hit + miss) if (hit + miss) else None


def fwd_cache_hit_rate() -> Optional[float]:
    """hit / (hit + miss) over the compiled-forward no-grad cache, or
    None before any no-grad dispatch ran with the cache enabled."""
    hit = counter("fwd_cache.hit").value
    miss = counter("fwd_cache.miss").value
    return hit / (hit + miss) if (hit + miss) else None
