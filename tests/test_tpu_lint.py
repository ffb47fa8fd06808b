"""tpu_lint static-analysis suite (ISSUE 6 tentpole).

Tier-1 coverage of paddle_tpu/analysis:

- the repo itself is CLEAN under all four passes (geometry, donation,
  purity, flags) with zero unwaivered findings — the gate that keeps
  kernel geometry, donation contracts, and traced-code purity honest
  without chip time;
- per-site VMEM regression: the analyzer's predicted footprint for each
  of the 8 ``pallas_call`` sites equals an independently hand-written
  block list (analysis/sites.py), so analyzer drift OR a silent kernel
  geometry change fails here first;
- each geometry rule fires on a synthetic bad launch spec;
- the ``FLAGS_check_donation`` poison mode catches a deliberately
  injected use-after-donate (refcount guard bypassed) and stays silent
  when the guard does its job;
- the purity lint flags each hazard class and honors inline waivers;
- flags/env parity: every flag readable via ``PADDLE_TPU_*`` with
  ``FLAGS_*`` taking precedence.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import analysis
from paddle_tpu.analysis.audit import BlockSpecInfo, PallasCallRecord
from paddle_tpu.analysis.geometry import (analyze_record,
                                          tile_padded_bytes,
                                          vmem_footprint)
from paddle_tpu.analysis.purity import run_purity_file
from paddle_tpu.analysis.sites import KERNEL_SITES
from paddle_tpu.analysis.sites import trace_site as _trace_site_raw
from paddle_tpu.device import vmem as dvmem
from paddle_tpu.ops import dispatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SITE_RECORDS: dict = {}


def trace_site(site):
    """Each dry-trace is deterministic over a fixed inventory; four
    tests walking all sites re-traced everything — memoize per site
    (records are read-only)."""
    if site.name not in _SITE_RECORDS:
        _SITE_RECORDS[site.name] = _trace_site_raw(site)
    return _SITE_RECORDS[site.name]


# ---------------------------------------------------------------------
# the repo is clean (the acceptance gate)
# ---------------------------------------------------------------------

class TestRepoIsClean:
    def test_all_passes_zero_unwaivered_under_60s(self):
        t0 = time.time()
        results = analysis.run_all_passes()
        elapsed = time.time() - t0
        # 3 kernel-level (PR 6) + flags + 5 program-level (PR 7 +
        # the ISSUE 19 overlap-census pass)
        assert set(results) == set(analysis.PASS_NAMES) == {
            "geometry", "donation", "purity", "flags",
            "dtype", "sync", "memory", "spmd", "overlap"}
        for name, findings in results.items():
            live = analysis.unwaivered(findings)
            assert not live, (
                f"pass {name!r} has unwaivered findings:\n  "
                + "\n  ".join(f.render() for f in live))
        # acceptance criterion: the full run fits in the CI budget
        assert elapsed < 60, f"tpu_lint took {elapsed:.1f}s (>60s)"

    def test_cli_json_report_and_baseline_ratchet(self, tmp_path):
        """One CLI run: schema-v2 JSON report (waived findings carry
        their reasons) + --write-baseline, then the ratchet compare
        against the fresh baseline passes by construction."""
        base = tmp_path / "lint_base.json"
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "tpu_lint.py"),
             "--json", "--write-baseline", str(base)],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        report = json.loads(proc.stdout)
        assert report["schema_version"] == 2
        assert report["ok"] is True
        assert report["unwaivered"] == 0
        assert set(report["passes"]) == set(analysis.PASS_NAMES)
        # audit trail: waived findings listed with reasons
        for f in report["waived_findings"]:
            assert f["waived"] and f["waive_reason"]
        assert report["waived"] == len(report["waived_findings"])
        # the baseline stub holds per-rule unwaivered counts (clean
        # tree -> {}) and ratchets in-process
        doc = json.loads(base.read_text())
        assert doc["rule_counts"] == report["rule_counts"] == {}
        assert analysis.ratchet(report["rule_counts"],
                                doc["rule_counts"]) == []


# ---------------------------------------------------------------------
# geometry: site coverage + footprint regression
# ---------------------------------------------------------------------

class TestKernelSites:
    def test_all_sites_dry_trace(self):
        assert len(KERNEL_SITES) == 23
        for site in KERNEL_SITES:
            records = trace_site(site)
            assert len(records) == site.n_calls
            rec = records[0]
            assert rec.grid, f"{site.name}: empty grid"
            assert rec.operands, f"{site.name}: no operand avals"

    def test_footprint_matches_hand_block_list(self):
        """Analyzer prediction == independent hand-written block list,
        per site — guards analyzer drift when kernels change."""
        for site in KERNEL_SITES:
            records = trace_site(site)
            got = sum(vmem_footprint(r).total_bytes for r in records)
            if site.expected_vmem is None:  # stock jax flash kernel
                assert 0 < got <= dvmem.vmem_budget_bytes(), site.name
                continue
            assert got == site.expected_vmem(), (
                f"{site.name}: analyzer footprint {got:,} != hand "
                f"block list {site.expected_vmem():,} — kernel "
                "geometry or the footprint model changed; reconcile "
                "analysis/sites.py")

    def test_repo_kernels_within_declared_limits(self):
        for site in KERNEL_SITES:
            for rec in trace_site(site):
                fp = vmem_footprint(rec).total_bytes
                limit = (rec.vmem_limit_bytes
                         or dvmem.MOSAIC_DEFAULT_VMEM_LIMIT_BYTES)
                assert fp <= limit, (site.name, fp, limit)

    def test_repo_kernel_limits_derive_from_budget_table(self):
        # the satellite: the 100 MiB caps are the named constant now
        assert dvmem.KERNEL_VMEM_LIMIT_BYTES == (
            dvmem.VMEM_BUDGET_BYTES[dvmem.DEFAULT_GENERATION]
            - dvmem.VMEM_RESERVE_BYTES) == 100 * 2 ** 20
        declared = [rec.vmem_limit_bytes
                    for site in KERNEL_SITES
                    if "flash" not in site.name
                    for rec in trace_site(site)]
        assert declared and all(
            v == dvmem.KERNEL_VMEM_LIMIT_BYTES for v in declared)


def _rec(in_specs, operands, out_specs=(), out_shapes=(), grid=(4,),
         scratch=(), vmem=None):
    return PallasCallRecord(
        kernel_name="k", path="synthetic.py", line=1, grid=grid,
        num_scalar_prefetch=0, in_specs=list(in_specs),
        out_specs=list(out_specs), scratch=list(scratch),
        out_shapes=list(out_shapes), vmem_limit_bytes=vmem,
        input_output_aliases={}, interpret=False,
        operands=list(operands))


class TestGeometryRules:
    def test_tile_padding_model(self):
        assert tile_padded_bytes((8, 128), "float32") == 8 * 128 * 4
        # sublane pad: bf16 needs 16 sublanes, int8 needs 32
        assert tile_padded_bytes((8, 128), "bfloat16") == 16 * 128 * 2
        assert tile_padded_bytes((8, 128), "int8") == 32 * 128
        # lane pad: last dim 1 -> 128
        assert tile_padded_bytes((8, 1), "float32") == 8 * 128 * 4
        # leading dims multiply unpadded
        assert tile_padded_bytes((3, 8, 128), "float32") == 3 * 8 * 128 * 4

    def test_tile_misalignment_flagged(self):
        rec = _rec(
            [BlockSpecInfo((8, 130), lambda i: (0, i), None)],
            [((8, 520), "float32")])
        assert any(f.rule == "G-TILE" for f in analyze_record(rec))

    def test_divisibility_flagged(self):
        rec = _rec(
            [BlockSpecInfo((8, 128), lambda i: (0, 0), None)],
            [((8, 500), "float32")])
        assert any(f.rule == "G-DIV" for f in analyze_record(rec))

    def test_index_map_out_of_bounds_at_grid_edge(self):
        rec = _rec(
            [BlockSpecInfo((8, 128), lambda i: (0, i), None)],
            [((8, 256), "float32")])  # grid (4,) -> block 2 maps past N
        assert any(f.rule == "G-BOUNDS" for f in analyze_record(rec))

    def test_vmem_overflow_flagged_against_mosaic_default(self):
        big = BlockSpecInfo((8, 4 * 2 ** 20), lambda i: (0, i), None)
        rec = _rec([big], [((8, 16 * 2 ** 20), "float32")])
        assert any(f.rule == "G-VMEM" for f in analyze_record(rec))

    def test_budget_overflow_flagged(self):
        rec = _rec(
            [BlockSpecInfo((8, 128), lambda i: (0, 0), None)],
            [((8, 128), "float32")], vmem=200 * 2 ** 20)
        assert any(f.rule == "G-BUDGET"
                   for f in analyze_record(rec, generation="v5e"))
        # and a 100 MiB declared limit cannot fit a v3
        rec2 = _rec(
            [BlockSpecInfo((8, 128), lambda i: (0, 0), None)],
            [((8, 128), "float32")],
            vmem=dvmem.KERNEL_VMEM_LIMIT_BYTES)
        assert any(f.rule == "G-BUDGET"
                   for f in analyze_record(rec2, generation="v3"))

    def test_streamed_blocks_double_buffered(self):
        streamed = _rec(
            [BlockSpecInfo((8, 128), lambda i: (0, i), None)],
            [((8, 512), "float32")])
        resident = _rec(
            [BlockSpecInfo((8, 128), lambda i: (0, 0), None)],
            [((8, 512), "float32")])
        assert (vmem_footprint(streamed).total_bytes
                == 2 * vmem_footprint(resident).total_bytes)

    def test_magic_literal_scan_clean_and_fires(self, tmp_path):
        assert analysis.scan_magic_vmem_literals(
            os.path.join(REPO, "paddle_tpu")) == []
        bad = tmp_path / "pkg" / "bad.py"
        bad.parent.mkdir()
        bad.write_text("f(vmem_limit_bytes=100 * 1024 * 1024)\n")
        found = analysis.scan_magic_vmem_literals(str(bad.parent))
        assert [f.rule for f in found] == ["G-MAGIC"]


# ---------------------------------------------------------------------
# donation: poison mode + static audit
# ---------------------------------------------------------------------

class TestUseAfterDonate:
    def _fresh(self):
        dispatch._FWD_SEEN.clear()
        dispatch._FWD_CACHE.clear()
        analysis.clear_poisoned()

    def test_poison_mode_catches_injected_use_after_donate(self):
        """Bypass the refcount guard (the injected bug) and hold an
        alias across a donating call: the poisoned read must raise."""
        self._fresh()
        orig_guard = dispatch._donation_safe
        paddle.set_flags({"FLAGS_check_donation": True})
        dispatch._donation_safe = lambda arrays, i: True
        try:
            x = paddle.to_tensor(
                np.random.randn(8, 8).astype(np.float32))
            F.relu_(x)   # sighting
            F.relu_(x)   # admitted: compiled with donation
            alias = x.detach()          # aliases x's current buffer
            F.relu_(x)   # cache hit donates the aliased buffer
            assert analysis.poisoned_count() >= 1
            with pytest.raises(analysis.UseAfterDonateError):
                alias.numpy()
            with pytest.raises(analysis.UseAfterDonateError):
                F.relu(alias)           # dispatch-entry check too
        finally:
            dispatch._donation_safe = orig_guard
            paddle.set_flags({"FLAGS_check_donation": False})
            self._fresh()

    def test_refcount_guard_prevents_false_positive(self):
        """With the real guard, a held alias suppresses donation — the
        poison mode must stay silent and values must be correct."""
        self._fresh()
        paddle.set_flags({"FLAGS_check_donation": True})
        try:
            src = np.random.randn(8, 8).astype(np.float32)
            for _ in range(3):
                x = paddle.to_tensor(src)
                alias = x.detach()
                F.relu_(x)
                np.testing.assert_array_equal(alias.numpy(), src)
        finally:
            paddle.set_flags({"FLAGS_check_donation": False})
            self._fresh()

    def test_poison_registry_purges_on_death(self):
        self._fresh()
        import jax.numpy as jnp

        a = jnp.ones((4,))
        analysis.poison(a, "t")
        assert analysis.is_poisoned(a) == "t"
        assert analysis.poisoned_count() == 1
        del a
        import gc

        gc.collect()
        assert analysis.poisoned_count() == 0

    def test_registry_audit_clean_and_detects_bad_contract(self):
        from paddle_tpu.ops import registry

        assert analysis.run_donation_pass() == []
        registry._REGISTRY["__lint_bad_op__"] = registry.OpDef(
            "__lint_bad_op__", lambda x: x, donates=(0, 1))
        try:
            rules = {f.rule for f in analysis.run_donation_pass()}
            assert {"D-SLOT", "D-ORPHAN", "D-TAG"} <= rules
        finally:
            registry._REGISTRY.pop("__lint_bad_op__")
        assert analysis.run_donation_pass() == []

    def test_inplace_family_contracts_complete(self):
        from paddle_tpu.ops.registry import all_ops

        ops = all_ops()
        for name in ("relu_", "tanh_", "elu_", "softmax_", "reshape_",
                     "increment_"):
            d = ops[name]
            assert d.donates == (0,), name
            assert d.inplace_of in ops, (name, d.inplace_of)


# ---------------------------------------------------------------------
# purity lint
# ---------------------------------------------------------------------

_BAD_TRACED = '''\
import random
import time

import jax
import numpy as np


def outer(n, x0):
    acc = []

    def body(i, carry):
        if carry > 0:
            carry = carry + 1
        t = time.time()
        r = random.random()
        v = float(carry)
        a = np.abs(carry)
        acc.append(i)
        return carry + t + r + v + a

    return jax.lax.fori_loop(0, n, body, x0)


def waived(n, x0):
    def body(i, carry):
        r = random.random()  # tpu-lint: ok(P-HOST-RNG) -- test fixture
        return carry + r

    return jax.lax.fori_loop(0, n, body, x0)


def fine(n, x0):
    def body(i, carry):
        if i is None:
            return carry
        k = len(carry)
        return carry * k

    return jax.lax.fori_loop(0, n, body, x0)
'''


class TestPurityLint:
    def test_each_hazard_class_fires(self, tmp_path):
        p = tmp_path / "bad_traced.py"
        p.write_text(_BAD_TRACED)
        findings = run_purity_file(str(p), "bad_traced.py")
        rules = {f.rule for f in findings if not f.waived}
        assert {"P-TRACER-IF", "P-HOST-TIME", "P-HOST-RNG",
                "P-CONCRETIZE", "P-NP-TRACER", "P-STATE-MUT"} <= rules

    def test_waiver_honored_with_reason(self, tmp_path):
        p = tmp_path / "bad_traced.py"
        p.write_text(_BAD_TRACED)
        findings = run_purity_file(str(p), "bad_traced.py")
        waived = [f for f in findings if f.waived]
        assert len(waived) == 1
        assert waived[0].rule == "P-HOST-RNG"
        assert "test fixture" in waived[0].waive_reason

    def test_bare_waiver_flagged(self, tmp_path):
        p = tmp_path / "w.py"
        p.write_text("x = 1  # tpu-lint: ok(P-HOST-RNG)\n")
        findings = run_purity_file(str(p), "w.py")
        assert [f.rule for f in findings] == ["P-WAIVER"]

    def test_static_accessors_not_flagged(self, tmp_path):
        p = tmp_path / "bad_traced.py"
        p.write_text(_BAD_TRACED)
        findings = run_purity_file(str(p), "bad_traced.py")
        # `fine()` uses is-None identity + len(): both python-static
        fine_lines = [i for i, l in enumerate(_BAD_TRACED.splitlines(),
                                              1) if "def fine" in l]
        assert not [f for f in findings if f.line >= fine_lines[0]]


# ---------------------------------------------------------------------
# flags/env parity
# ---------------------------------------------------------------------

class TestFlagsParity:
    def test_paddle_tpu_env_override(self, monkeypatch):
        from paddle_tpu.core import flags as fl

        name = "t_lint_env_demo"
        monkeypatch.setenv(fl.env_var_for(name), "5")
        try:
            fl.define_flag(name, 0, "test-only")
            assert fl.flag(name) == 5
        finally:
            fl._FLAGS.pop(name, None)

    def test_flags_env_wins_over_paddle_tpu(self, monkeypatch):
        from paddle_tpu.core import flags as fl

        name = "t_lint_env_prec"
        monkeypatch.setenv(f"FLAGS_{name}", "1")
        monkeypatch.setenv(fl.env_var_for(name), "2")
        try:
            fl.define_flag(name, 0, "test-only")
            assert fl.flag(name) == 1
        finally:
            fl._FLAGS.pop(name, None)

    def test_every_flag_has_readme_row(self):
        assert analysis.run_flags_pass(REPO) == []

    def test_missing_row_detected(self, tmp_path):
        from paddle_tpu.core import flags as fl

        name = "t_lint_readme_hole"
        try:
            fl.define_flag(name, 0, "test-only")
            findings = analysis.run_flags_pass(REPO)
            assert any(f.rule == "F-README"
                       and name in (f.site or "") for f in findings)
        finally:
            fl._FLAGS.pop(name, None)
