"""Pass 6 — MEMORY: static HBM-peak estimator (liveness over jaxprs).

"This 13B config OOMs on v5e" should be a CPU-side lint finding, not a
burned 25-minute chip session. The estimator walks a program's closed
jaxpr in execution order tracking live buffer bytes:

- program inputs are live from entry; DONATED inputs (the engine's KV
  cache, TrainStep's param/opt-state buffers — ``donate_argnums``) die
  at their last use (XLA aliases their pages into outputs), while
  non-donated inputs stay live to the end (the caller holds them);
- each equation's outputs allocate while its inputs are still live
  (that overlap is exactly where real peaks live);
- intermediates die after their last use;
- control-flow bodies (scan/while/cond/pjit) contribute their own
  inner peak NET of their boundary values (carries are already counted
  at the outer level).

The resulting ``peak_bytes`` is an upper bound that ignores XLA fusion
(fused elementwise chains never materialize) — tight in practice
because programs here are dominated by weights/caches, not elementwise
temps; the tier-1 test pins it within 20% of
``compiled.memory_analysis()`` for the decode program.

``M-HBM`` fires when a program's peak exceeds the per-generation HBM
capacity table (``device.vmem.HBM_BUDGET_BYTES`` minus the runtime
reserve).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from .base import Finding, waive_from_sources
from .jaxpr_util import aval_bytes, repo_root, sub_jaxprs

__all__ = ["HbmEstimate", "peak_live_bytes", "estimate_program",
           "run_memory_pass"]


@dataclasses.dataclass
class HbmEstimate:
    peak_bytes: int          # max live bytes at any execution point
    arg_bytes: int           # program inputs (incl. consts)
    out_bytes: int           # program outputs
    n_eqns: int

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def _jaxpr_peak(jaxpr, donated_invars=frozenset(),
                const_bytes: int = 0) -> Tuple[int, int]:
    """(peak_bytes, boundary_bytes) of one jaxpr. ``donated_invars`` are
    flat invar INDICES whose buffers may die at last use."""
    from jax.extend.core import Var

    last_use: Dict[object, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if isinstance(v, Var):
                last_use[v] = i
    outset = {v for v in jaxpr.outvars if isinstance(v, Var)}
    donated = {v for i, v in enumerate(jaxpr.invars)
               if i in donated_invars}

    live: Dict[object, int] = {}
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        live[v] = aval_bytes(v.aval)
    cur = sum(live.values()) + const_bytes
    peak = cur
    for i, eqn in enumerate(jaxpr.eqns):
        out_b = sum(aval_bytes(v.aval) for v in eqn.outvars)
        inner_extra = 0
        for sj in sub_jaxprs(eqn):
            # inner bodies may donate everything: their carries are the
            # outer eqn's operands, counted here already
            p, boundary = _jaxpr_peak(
                sj, donated_invars=frozenset(range(len(sj.invars))))
            inner_extra = max(inner_extra, max(0, p - boundary))
        peak = max(peak, cur + out_b + inner_extra)
        for v in eqn.outvars:
            live[v] = aval_bytes(v.aval)
            cur += live[v]
        for v in {v for v in eqn.invars if isinstance(v, Var)}:
            if last_use.get(v) != i or v in outset or v not in live:
                continue
            if v in jaxpr.invars and v not in donated:
                continue  # caller still holds a non-donated input
            cur -= live.pop(v)
    boundary = (sum(aval_bytes(v.aval) for v in jaxpr.invars)
                + sum(aval_bytes(v.aval) for v in jaxpr.constvars)
                + sum(aval_bytes(v.aval) for v in jaxpr.outvars
                      if isinstance(v, Var)))
    return peak, boundary


def peak_live_bytes(closed, donated_invars=frozenset()) -> HbmEstimate:
    """Donation-aware peak-live-bytes bound for a ClosedJaxpr."""
    jaxpr = closed.jaxpr
    const_bytes = sum(aval_bytes(getattr(c, "aval", None)) or
                      getattr(c, "nbytes", 0) for c in closed.consts)
    peak, _ = _jaxpr_peak(jaxpr, donated_invars=donated_invars,
                          const_bytes=const_bytes)
    return HbmEstimate(
        peak_bytes=int(peak),
        arg_bytes=int(sum(aval_bytes(v.aval) for v in jaxpr.invars)
                      + const_bytes),
        out_bytes=int(sum(aval_bytes(getattr(v, "aval", None))
                          for v in jaxpr.outvars)),
        n_eqns=len(jaxpr.eqns))


def estimate_program(traced) -> HbmEstimate:
    """Estimate for one :class:`TracedProgram` (donation-aware)."""
    return peak_live_bytes(traced.closed,
                           donated_invars=traced.donated_invars)


def run_memory_pass(generation: Optional[str] = None,
                    traced: Optional[Dict] = None) -> List[Finding]:
    """M-HBM findings over the program inventory, against the HBM
    capacity of ``generation`` (default: attached chip, else v5e)."""
    from ..device import vmem as dv
    from .program_sites import trace_all_programs

    if traced is None:
        traced = trace_all_programs()
    budget = dv.hbm_budget_bytes(generation)
    gen = generation or dv.detect_generation()
    findings: List[Finding] = []
    for tp in traced.values():
        est = estimate_program(tp)
        if est.peak_bytes <= budget:
            continue
        site = tp.site
        findings.append(Finding(
            rule="M-HBM", site=site.name, path=site.path, line=site.line,
            message=(f"static peak-live estimate "
                     f"{est.peak_bytes / dv.GiB:.2f} GiB for "
                     f"`{site.name}` exceeds the {gen} usable HBM "
                     f"{budget / dv.GiB:.1f} GiB "
                     f"({dv.HBM_BUDGET_BYTES.get(gen, 0) / dv.GiB:.0f} "
                     "GiB capacity - runtime reserve) — this program "
                     "OOMs on the chip")))
    return waive_from_sources(findings, repo_root())
