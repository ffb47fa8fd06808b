"""paddle_tpu.analysis — static analysis for TPU kernels and traced
code, runnable entirely on CPU.

Chip time is the scarcest resource in this repo; this package proves
on CPU the properties that otherwise only fail on hardware:

- **Pass 1 — kernel geometry** (:mod:`.geometry` over :mod:`.audit` /
  :mod:`.sites`): every ``pallas_call`` launch spec is shim-recorded
  from an ``eval_shape`` dry-trace and validated — VMEM footprint vs
  the declared limit and the per-generation budget table
  (:mod:`paddle_tpu.device.vmem`), dtype tile alignment, grid
  divisibility, index-map bounds at grid edges, and no magic
  ``vmem_limit_bytes`` literals.
- **Pass 2 — use-after-donate** (:mod:`.donation`): a
  ``FLAGS_check_donation`` poison mode that makes CPU runs fail exactly
  where TPU donation would read freed HBM, plus a static audit of the
  registry's donation contracts.
- **Pass 3 — trace purity** (:mod:`.purity`): AST lint of traced code
  for concretization hazards (``bool/int/float``/``if`` on tracers,
  ``np.*`` on tracers, host time/RNG, python-state mutation in loop
  bodies), with an inline waiver syntax
  (``# tpu-lint: ok(<rule>) -- <reason>``).

PR 7 extends the suite one level up — from kernels to whole compiled
PROGRAMS (:mod:`.program_sites` dry-traces the repo's jit'd composites,
the train step, and the serving prefill/decode programs to closed
jaxprs):

- **Pass 4 — DTYPE** (:mod:`.dtype_flow`): silent bf16→f32 matmul
  promotion in declared-bf16 programs (``X-PROMOTE``) and f64 leakage
  (``X-F64``).
- **Pass 5 — SYNC** (:mod:`.host_sync`): host callbacks inside hot
  loops / decode programs (``X-SYNC``) and recompile-churn statics
  (``X-CHURN``).
- **Pass 6 — MEMORY** (:mod:`.hbm`): donation-aware liveness walk →
  static HBM-peak bound per program, vs the per-generation capacity
  table in ``device.vmem`` (``M-HBM``).
- **Pass 7 — SPMD** (:mod:`.spmd`): the distributed surfaces compiled
  on a virtual 8-device CPU mesh; undeclared collectives in the
  partitioned HLO (``S-GATHER``), asymmetric collective sequences
  across branches (``S-MATCH``), missing output sharding constraints
  (``S-UNSPEC``).
- **Pass 9 — OVERLAP** (:mod:`.overlap`): the comm/compute overlap
  sites (ring-reduce TP decode, double-buffered EP exchange) keep
  their exact collective census — phase counts, permute ordering, no
  stray blocking psum (``S-OVERLAP``).

Front-end: ``tools/tpu_lint.py`` (``--json`` for CI, ``--baseline``
ratchet); :mod:`.preflight` gates the bench/profiling drivers; the
tier-1 tests ``tests/test_tpu_lint.py`` + ``tests/test_graph_lint.py``
assert the repo is clean.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional

from .audit import PallasCallRecord, record_pallas_calls  # noqa: F401
from .base import (  # noqa: F401
    Finding, apply_waivers, parse_waivers, waive_from_sources,
)
from .donation import (  # noqa: F401
    UseAfterDonateError, assert_not_poisoned, audit_donation_registry,
    clear_poisoned, is_poisoned, poison, poisoned_count,
)
from .dtype_flow import check_dtype_flow, run_dtype_pass  # noqa: F401
from .flags_lint import env_var_for, run_flags_pass  # noqa: F401
from .geometry import (  # noqa: F401
    analyze_record, scan_magic_vmem_literals, tile_padded_bytes,
    vmem_footprint,
)
from .hbm import (  # noqa: F401
    estimate_program, peak_live_bytes, run_memory_pass,
)
from .host_sync import run_sync_pass  # noqa: F401
from .program_sites import (  # noqa: F401
    PROGRAM_SITES, ProgramSite, TracedProgram, site_for_fn,
    trace_all_programs, trace_program,
)
from .purity import run_purity_pass  # noqa: F401
from .sites import KERNEL_SITES, trace_all_sites, trace_site  # noqa: F401
from .overlap import (  # noqa: F401
    OVERLAP_SITES, OverlapSite, check_overlap_program,
    run_overlap_pass,
)
from .spmd import (  # noqa: F401
    SPMD_SITES, SpmdSite, check_spmd_site, hlo_collective_counts,
    mesh_available, run_spmd_pass, trace_census, virtual_mesh,
)

__all__ = [
    "Finding", "PallasCallRecord", "record_pallas_calls",
    "UseAfterDonateError", "poison", "is_poisoned", "assert_not_poisoned",
    "poisoned_count", "clear_poisoned",
    "analyze_record", "vmem_footprint", "tile_padded_bytes",
    "scan_magic_vmem_literals", "audit_donation_registry",
    "run_geometry_pass", "run_donation_pass", "run_purity_pass",
    "run_flags_pass", "run_dtype_pass", "run_sync_pass",
    "run_memory_pass", "run_spmd_pass", "run_all_passes",
    "run_program_passes", "unwaivered", "rule_counts", "ratchet",
    "KERNEL_SITES", "trace_site", "trace_all_sites", "env_var_for",
    "PROGRAM_SITES", "ProgramSite", "TracedProgram", "site_for_fn",
    "trace_program", "trace_all_programs", "estimate_program",
    "peak_live_bytes", "SPMD_SITES", "SpmdSite", "check_spmd_site",
    "hlo_collective_counts", "mesh_available", "virtual_mesh",
    "waive_from_sources", "PASS_NAMES", "trace_census",
    "OVERLAP_SITES", "OverlapSite", "check_overlap_program",
    "run_overlap_pass",
]

#: every pass, in report order: 3 kernel-level + flags (PR 6), the
#: 4 program-level passes (PR 7), and the overlap-structure pass
#: (ISSUE 19)
PASS_NAMES = ("geometry", "donation", "purity", "flags",
              "dtype", "sync", "memory", "spmd", "overlap")


def _pkg_root() -> str:
    """The paddle_tpu/ package directory."""
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_geometry_pass(generation: Optional[str] = None) -> List[Finding]:
    """Dry-trace every kernel site, analyze each recorded launch spec,
    and scan the tree for magic VMEM literals."""
    pkg = _pkg_root()
    findings: List[Finding] = []
    for name, records in trace_all_sites().items():
        for rec in records:
            for f in analyze_record(rec, generation=generation):
                f.site = f"{name} ({rec.kernel_name})"
                findings.append(f)
    src_findings = scan_magic_vmem_literals(pkg)
    waivers = {}
    for f in src_findings:
        if f.path and f.path not in waivers:
            path = os.path.join(os.path.dirname(pkg), f.path)
            try:
                with open(path, encoding="utf-8") as fh:
                    waivers[f.path] = parse_waivers(fh.read())
            except OSError:
                pass
    apply_waivers(src_findings, waivers)
    return findings + src_findings


def run_donation_pass() -> List[Finding]:
    return audit_donation_registry(_pkg_root())


def run_program_passes(generation: Optional[str] = None
                       ) -> Dict[str, List[Finding]]:
    """The four program-level checks (PR 7); the program inventory is
    traced ONCE and shared across dtype/sync/memory."""
    traced = trace_all_programs()
    return {
        "dtype": run_dtype_pass(traced=traced),
        "sync": run_sync_pass(traced=traced),
        "memory": run_memory_pass(generation=generation, traced=traced),
        "spmd": run_spmd_pass(),
        "overlap": run_overlap_pass(),
    }


def run_all_passes(generation: Optional[str] = None
                   ) -> Dict[str, List[Finding]]:
    """All checks; keys = ``PASS_NAMES`` (kernel-level geometry /
    donation / purity / flags + program-level dtype / sync / memory /
    spmd)."""
    out = {
        "geometry": run_geometry_pass(generation=generation),
        "donation": run_donation_pass(),
        "purity": run_purity_pass(_pkg_root()),
        "flags": run_flags_pass(),
    }
    out.update(run_program_passes(generation=generation))
    return out


def unwaivered(findings: List[Finding]) -> List[Finding]:
    return [f for f in findings if not f.waived]


def rule_counts(results: Dict[str, List[Finding]]) -> Dict[str, int]:
    """rule id -> UNWAIVERED finding count (the ratchet currency —
    waived legacy findings never count against a baseline)."""
    counts: Dict[str, int] = {}
    for fs in results.values():
        for f in unwaivered(fs):
            counts[f.rule] = counts.get(f.rule, 0) + 1
    return counts


def ratchet(current: Dict[str, int], baseline: Dict[str, int]
            ) -> List[str]:
    """Ratchet compare: lines describing every rule whose unwaivered
    count GREW vs the baseline (empty = no new findings; shrinkage and
    baseline-only rules are fine — the ratchet only tightens)."""
    bad = []
    for rule in sorted(current):
        cur, base = current[rule], baseline.get(rule, 0)
        if cur > base:
            bad.append(f"{rule}: {base} -> {cur} (+{cur - base} new)")
    return bad
