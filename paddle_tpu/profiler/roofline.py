"""XLA cost-model roofline analysis: measured wall time vs hardware peaks.

Device-level observability the host-side tracer cannot provide (SURVEY
§1 layer 1/3: the reference derives per-op statistic tables and
device/memory views from its tracer, profiler_statistic.py). On TPU the
compiler already knows every program's arithmetic and memory traffic —
``compiled.cost_analysis()`` reports FLOPs and bytes accessed straight
from XLA's cost model — so instead of asserting "decode runs at 35% of
the weight-bandwidth roofline" from a hand-derived byte count, every
compiled program records its model-derived cost here and any honest
wall-time measurement turns it into achieved FLOP/s, achieved bytes/s,
MFU, and %-of-bandwidth-roofline.

Three cooperating pieces:

- ``record_program(name, compiled)`` — read the XLA cost model of a
  compiled executable into the per-program table and the
  ``compile.{flops,bytes}`` stats gauges. The jit layers
  (jit/static_function.py, jit/train_step.py) and the inference decode
  step call this automatically at compile time via ``AotProgram``.
- ``analyze(name, wall_s)`` — fold a measured wall time into achieved
  rates against the one chip table (``device/chip.py``, keyed by
  ``device_kind``, env-overridable) and publish ``roofline.*`` gauges.
  Off-TPU there is no peak: utilizations are None, rates are kept.
- ``AotProgram`` — a thin wrapper that turns a ``jax.jit`` function
  into an explicitly compiled executable (``lower().compile()``) so the
  cost model is captured WITHOUT a second compilation; falls back to
  the plain jitted call path on any AOT mismatch.

Honesty note: rates are only as good as the wall time fed in, and the
program never feeds one that is not synced: ``AotProgram`` records cost
at compile time and opens a ``pt.program:<name>`` span around each call
(signature + enqueue; the device's time is in the profiler's trace),
the serving step ``analyze``s with the wall time its token fetch
synced, and the bench entry points (bench.py, tools/*_profile.py)
``analyze`` with their own synced timings, which are what lands in
BENCH_*.json.
"""
from __future__ import annotations

import functools
import os
from typing import Dict, Optional

import jax

from . import stats as _stats
from .profiler import RecordEvent

__all__ = [
    "device_peaks", "program_cost",
    "record_program", "analyze", "report", "reset",
    "RooflineResult", "AotProgram", "format_report",
]

#: env overrides (floats, FLOP/s and bytes/s) — let a deployment pin
#: the exact part's numbers without a code change
ENV_PEAK_FLOPS = "PADDLE_TPU_PEAK_FLOPS"
ENV_PEAK_HBM_BW = "PADDLE_TPU_PEAK_HBM_BW"

#: per-program cost/rate table: name -> {"flops", "bytes", "wall_s",
#: "achieved_flops_per_s", "achieved_bytes_per_s", "mfu", "bw_util"}
_PROGRAMS: Dict[str, dict] = {}


@functools.lru_cache(maxsize=1)
def _default_device():
    # analyze() runs once per synced step: look it up once
    return jax.devices()[0]


def device_peaks(device=None):
    """(peak FLOP/s, peak HBM bytes/s) for the device: env override,
    else the one chip table (``paddle_tpu.device.chip.CHIPS``, keyed by
    ``device_kind``). A device that is not in the table — a CPU
    included — raises: no other chip's peaks are assumed for it."""
    from ..device.chip import chip_spec

    env_f = os.environ.get(ENV_PEAK_FLOPS)
    env_b = os.environ.get(ENV_PEAK_HBM_BW)
    if env_f and env_b:
        return float(env_f), float(env_b)
    spec = chip_spec(device if device is not None else _default_device())
    return (float(env_f) if env_f else spec.peak_bf16_flops,
            float(env_b) if env_b else spec.hbm_bytes_per_s)


def _peaks_or_none(device):
    """Peaks for ``analyze``: off-TPU (the CPU test hosts) there is no
    roofline to compare with, so utilizations are reported as None and
    only the achieved rates are kept — unless both env overrides pin
    the peaks. On a TPU an unknown chip raises (``device_peaks``)."""
    if device is None:
        device = _default_device()
    pinned = os.environ.get(ENV_PEAK_FLOPS) \
        and os.environ.get(ENV_PEAK_HBM_BW)
    if device.platform != "tpu" and not pinned:
        return None, None
    return device_peaks(device)


def program_cost(compiled) -> Optional[dict]:
    """{"flops", "bytes"} from an executable's XLA cost analysis, or
    None when the backend exposes none. Handles both the list-of-dicts
    (one per computation) and plain-dict shapes ``cost_analysis()``
    returns across jax versions."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if ca is None:
        return None
    if isinstance(ca, (list, tuple)):
        if not ca:
            return None
        flops = sum(float(d.get("flops", 0.0)) for d in ca)
        nbytes = sum(float(d.get("bytes accessed", 0.0)) for d in ca)
    else:
        flops = float(ca.get("flops", 0.0))
        nbytes = float(ca.get("bytes accessed", 0.0))
    if flops <= 0.0 and nbytes <= 0.0:
        return None
    return {"flops": flops, "bytes": nbytes}


def record_program(name: str, compiled=None, *, flops=None,
                   bytes_accessed=None) -> Optional[dict]:
    """Register a compiled program's cost-model numbers. Either pass
    the executable (cost read via ``cost_analysis()``) or explicit
    flops/bytes. Publishes ``compile.flops`` / ``compile.bytes`` gauges
    (most recent program) and keeps the per-program table for
    ``analyze``/``report``."""
    cost = None
    if compiled is not None:
        cost = program_cost(compiled)
    elif flops is not None or bytes_accessed is not None:
        cost = {"flops": float(flops or 0.0),
                "bytes": float(bytes_accessed or 0.0)}
    if cost is None:
        return None
    entry = _PROGRAMS.setdefault(name, {})
    entry.update(cost)
    _stats.set_gauge("compile.flops", cost["flops"])
    _stats.set_gauge("compile.bytes", cost["bytes"])
    _stats.inc("compile.programs_analyzed")
    return dict(cost)


class RooflineResult:
    """Achieved rates for one program against the device peaks."""

    __slots__ = ("name", "flops", "bytes", "wall_s",
                 "achieved_flops_per_s", "achieved_bytes_per_s",
                 "mfu", "bw_util", "peak_flops", "peak_bw")

    def __init__(self, name, flops, nbytes, wall_s, peak_flops, peak_bw):
        self.name = name
        self.flops = flops
        self.bytes = nbytes
        self.wall_s = wall_s
        self.peak_flops = peak_flops
        self.peak_bw = peak_bw
        self.achieved_flops_per_s = flops / wall_s if wall_s > 0 else 0.0
        self.achieved_bytes_per_s = nbytes / wall_s if wall_s > 0 else 0.0
        # None, not 0.0, where the device has no known peak: a
        # utilization against nothing is not a small utilization
        self.mfu = (self.achieved_flops_per_s / peak_flops
                    if peak_flops else None)
        self.bw_util = (self.achieved_bytes_per_s / peak_bw
                        if peak_bw else None)

    def as_dict(self) -> dict:
        return {
            "flops": self.flops,
            "bytes": self.bytes,
            "wall_s": round(self.wall_s, 6),
            "achieved_flops_per_s": round(self.achieved_flops_per_s, 1),
            "achieved_bytes_per_s": round(self.achieved_bytes_per_s, 1),
            "mfu": None if self.mfu is None else round(self.mfu, 4),
            "bw_util": None if self.bw_util is None
            else round(self.bw_util, 4),
        }

    def format(self) -> str:
        return (_format_rates(self.name, self.achieved_flops_per_s,
                              self.mfu, self.achieved_bytes_per_s,
                              self.bw_util)
                + f" | cost: {self.flops:.3g} flops, "
                  f"{self.bytes:.3g} bytes @ {self.wall_s * 1e3:.3f} ms")


def _format_rates(name, flops_s, mfu, bytes_s, bw_util) -> str:
    def pct(v):
        return "not measured" if v is None else f"{100 * v:.1f}%"

    return (f"roofline[{name}]: {flops_s / 1e9:.1f} GFLOP/s "
            f"(MFU {pct(mfu)}) | {bytes_s / 1e9:.1f} GB/s "
            f"({pct(bw_util)} of HBM roofline)")


def analyze(name: str, wall_s: float, *, calls: int = 1,
            device=None) -> Optional[RooflineResult]:
    """Turn a measured wall time for ``calls`` executions of a recorded
    program into achieved rates; publishes the ``roofline.mfu`` and
    ``roofline.bw_util`` gauges (the most recently analyzed program,
    where the chip's peaks are known) and updates the per-program table
    with the achieved rates.
    Returns None when the program was never recorded or timing is
    degenerate."""
    entry = _PROGRAMS.get(name)
    if not entry or wall_s <= 0 or "flops" not in entry:
        return None
    per_call = wall_s / max(calls, 1)
    peak_flops, peak_bw = _peaks_or_none(device)
    res = RooflineResult(name, entry["flops"], entry["bytes"],
                         per_call, peak_flops, peak_bw)
    entry.update(res.as_dict())
    if res.mfu is not None:
        _stats.set_gauge("roofline.mfu", res.mfu)
        _stats.set_gauge("roofline.bw_util", res.bw_util)
    return res


def report() -> dict:
    """JSON-able copy of the per-program roofline table (programs with
    recorded cost; rates present once a wall time was analyzed)."""
    return {name: dict(entry) for name, entry in _PROGRAMS.items()}


def format_report() -> str:
    """One printable line per analyzed program (used by
    ``Profiler.summary()`` and the profile tools)."""
    lines = []
    for name, e in _PROGRAMS.items():
        if "mfu" in e:
            lines.append(_format_rates(
                name, e["achieved_flops_per_s"], e["mfu"],
                e["achieved_bytes_per_s"], e["bw_util"]))
        else:
            lines.append(f"roofline[{name}]: cost {e['flops']:.3g} flops"
                         f" / {e['bytes']:.3g} bytes (no timing yet)")
    return "\n".join(lines)


def reset() -> None:
    _PROGRAMS.clear()


def _aot_signature(args):
    """Hashable structure+aval key: pytree structure plus each leaf's
    (shape, dtype). Values of traced scalar leaves (python floats/ints,
    e.g. a learning-rate schedule) do NOT enter the key — they are
    traced operands, so the compiled program is value-independent."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return treedef, tuple(
        (tuple(getattr(leaf, "shape", ())),
         str(getattr(leaf, "dtype", type(leaf).__name__)),
         bool(getattr(leaf, "weak_type", not hasattr(leaf, "dtype"))))
        for leaf in leaves)


class AotProgram:
    """Explicit-AOT wrapper over a ``jax.jit`` function.

    First call per input signature does ``jitted.lower(*args).compile()``
    — the same single compilation jit would do, but through the AOT API
    so the executable (and its XLA cost model) is OURS to read — records
    the cost via ``record_program``, and dispatches the compiled object
    directly from then on. Any AOT failure (unsupported arg structure,
    signature drift, backend quirk) permanently falls back to the plain
    jitted call path for that signature, so behavior never regresses.

    Only wrap jitted functions whose every argument is traced (no
    ``static_argnums`` whose VALUES vary — the signature above is
    value-blind).
    """

    __slots__ = ("name", "_jitted", "_exes", "_failed")

    def __init__(self, name: str, jitted):
        self.name = name
        self._jitted = jitted
        self._exes: dict = {}
        self._failed: set = set()

    def __call__(self, *args):
        # signature + enqueue of the compiled program, under the
        # wrapper's own stable name (the device trace calls the same
        # program ``jit__<function>``: PERF.md 7.2)
        with RecordEvent("program:" + self.name):
            return self._call(*args)

    def _call(self, *args):
        try:
            sig = _aot_signature(args)
        except Exception:
            return self._jitted(*args)
        exe = self._exes.get(sig)
        if exe is None and sig not in self._failed:
            try:
                exe = self._jitted.lower(*args).compile()
                record_program(self.name, exe)
                self._exes[sig] = exe
            except Exception:
                # genuine trace errors re-raise below through the
                # jitted path, with its own diagnostics intact
                self._failed.add(sig)
                exe = None
        if exe is not None:
            try:
                return exe(*args)
            except Exception:
                self._exes.pop(sig, None)
                self._failed.add(sig)
        return self._jitted(*args)

    @property
    def jitted(self):
        """The underlying jit function (``lower_hlo``-style callers)."""
        return self._jitted
