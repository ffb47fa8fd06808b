"""Pass 5 — SYNC: host round-trips in hot loops + recompile churn.

A decode loop that hides one host callback runs at host latency
instead of chip latency (every scan iteration round-trips the host),
and a jit site keyed on an unhashable or per-step-varying static
recompiles every call — both are invisible in CPU runs and catastrophic
on the chip. Over the traced program inventory
(:mod:`.program_sites`):

- ``X-SYNC``: a host-callback-lowering primitive (``pure_callback`` /
  ``io_callback`` / ``debug_callback`` / ``debug_print`` — the lowering of
  ``jax.debug.print`` — and friends) inside a ``scan`` / ``while`` /
  ``fori_loop`` body, or ANYWHERE in a site marked ``hot_loop`` (the
  decode-step program: one sync per token is the whole latency budget).
- ``X-CHURN``: a program site whose declared jit static kwargs fail the
  dispatch layer's bakeable-statics discipline
  (``ops.dispatch._static_ok`` — the PR 3 admission-key helper): lists,
  dicts, arrays and Tensors are unhashable or freeze per-step values
  into the trace, i.e. a retrace storm or a stale constant.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from .base import Finding, waive_from_sources
from .jaxpr_util import eqn_anchor, repo_root, walk_eqns

__all__ = ["check_host_sync", "check_churn", "run_sync_pass"]

#: primitives that lower to a host round-trip
_CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                   "debug_print")


def check_host_sync(traced) -> List[Finding]:
    site = traced.site
    findings: List[Finding] = []
    for eqn, in_loop in walk_eqns(traced.closed.jaxpr):
        if eqn.primitive.name not in _CALLBACK_PRIMS:
            continue
        if not (in_loop or site.hot_loop):
            continue
        where = "a traced loop body" if in_loop else \
            f"the hot-loop program `{site.name}`"
        path, line = eqn_anchor(eqn)
        if path is None:
            path, line = site.path, site.line
        findings.append(Finding(
            rule="X-SYNC", site=site.name, path=path, line=line,
            message=(f"host callback `{eqn.primitive.name}` inside "
                     f"{where} — every execution round-trips the host "
                     "(host latency per decode step); hoist it out of "
                     "the compiled program")))
    return findings


def check_churn(site) -> List[Finding]:
    """X-CHURN over one site's declared static kwargs."""
    if not site.static_kwargs:
        return []
    from ..ops.dispatch import _static_ok

    bad = sorted(k for k, v in site.static_kwargs.items()
                 if not _static_ok(v))
    if not bad:
        return []
    return [Finding(
        rule="X-CHURN", site=site.name, path=site.path, line=site.line,
        message=(f"static kwarg(s) {bad} of `{site.name}` fail the "
                 "bakeable-statics allowlist (ops.dispatch._static_ok) "
                 "— unhashable or value-baking statics retrace the "
                 "program per call; pass them as traced operands or "
                 "hashable scalars"))]


def run_sync_pass(traced: Optional[Dict] = None) -> List[Finding]:
    """SYNC findings over the whole program inventory."""
    from .program_sites import trace_all_programs

    if traced is None:
        traced = trace_all_programs()
    findings: List[Finding] = []
    for tp in traced.values():
        findings += check_host_sync(tp)
        findings += check_churn(tp.site)
    return waive_from_sources(findings, repo_root())
