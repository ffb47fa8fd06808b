"""graph_lint: whole-program jaxpr/HLO analyzer (ISSUE 7 tentpole).

Tier-1 coverage of the four program-level passes:

- the repo's program inventory is CLEAN (dtype/sync/memory/spmd, zero
  unwaivered findings) within the 60s CI budget;
- every rule fires on a synthetic bad program AND an inline waiver
  silences it (X-PROMOTE, X-F64, X-SYNC, X-CHURN, M-HBM, S-GATHER,
  S-MATCH, S-UNSPEC);
- the MEMORY pass's donation-aware liveness model is pinned exactly on
  a known-peak chain, and the decode program's estimate lands within
  20% of ``compiled.memory_analysis()`` (acceptance criterion);
- the SPMD pass flags an injected missing-sharding-constraint
  all-gather on the virtual 8-device mesh (acceptance criterion);
- the preflight gate refuses on findings and honors --no-lint;
- the ratchet (per-rule counts) only tightens;
- bench_gate gates the new lint metrics.
"""
import importlib.util
import os
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import analysis
from paddle_tpu.analysis import site_for_fn, trace_program
from paddle_tpu.analysis.dtype_flow import check_dtype_flow
from paddle_tpu.analysis.hbm import peak_live_bytes
from paddle_tpu.analysis.host_sync import check_churn, check_host_sync
from paddle_tpu.analysis.spmd import SpmdSite, check_spmd_site
from paddle_tpu.device import vmem as dvmem

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _mod_from(tmp_path, name, source):
    """Import ``source`` as a module from a tmp file — synthetic bad
    programs live in real files so eqn anchoring + inline waivers work
    exactly as they do for repo code."""
    p = tmp_path / f"{name}.py"
    p.write_text(source)
    spec = importlib.util.spec_from_file_location(name, str(p))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------
# the repo is clean (the acceptance gate)
# ---------------------------------------------------------------------

class TestRepoProgramsClean:
    def test_program_passes_clean_under_60s(self):
        t0 = time.time()
        results = analysis.run_program_passes()
        elapsed = time.time() - t0
        assert set(results) == {"dtype", "sync", "memory", "spmd",
                                "overlap"}
        for name, findings in results.items():
            live = analysis.unwaivered(findings)
            assert not live, (
                f"pass {name!r} has unwaivered findings:\n  "
                + "\n  ".join(f.render() for f in live))
        assert elapsed < 60, f"program passes took {elapsed:.1f}s (>60s)"

    def test_program_inventory_traces(self):
        traced = analysis.trace_all_programs()
        assert {"dispatch.gelu", "jit.train_step", "inference.prefill",
                "inference.decode"} <= set(traced)
        for name, tp in traced.items():
            assert tp.closed.jaxpr.eqns, f"{name}: empty jaxpr"
        # donation declared for the serving programs (cache operands)
        assert traced["inference.decode"].donated_invars
        assert traced["jit.train_step"].donated_invars

    def test_lint_prefix_registered(self):
        from paddle_tpu.profiler import stats

        assert "lint." in stats.CONVENTION_PREFIXES


# ---------------------------------------------------------------------
# DTYPE: X-PROMOTE / X-F64
# ---------------------------------------------------------------------

class TestDtypePass:
    def test_injected_f32_upcast_flagged(self):
        def f(x, w):
            return x.astype(jnp.float32) @ w

        tp = trace_program(site_for_fn(
            "t.bad_promote", f,
            (_sds((8, 16), jnp.bfloat16), _sds((16, 4), jnp.float32)),
            compute_dtype="bfloat16"))
        assert any(fd.rule == "X-PROMOTE" for fd in check_dtype_flow(tp))

    def test_bf16_operands_with_f32_accumulation_pass(self):
        def f(x, w):
            return jax.lax.dot_general(
                x, w, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        tp = trace_program(site_for_fn(
            "t.accum_ok", f,
            (_sds((8, 16), jnp.bfloat16), _sds((16, 4), jnp.bfloat16)),
            compute_dtype="bfloat16"))
        assert check_dtype_flow(tp) == []

    def test_undeclared_site_not_promotion_checked(self):
        def f(x, w):
            return x.astype(jnp.float32) @ w

        tp = trace_program(site_for_fn(
            "t.f32_site", f,
            (_sds((8, 16), jnp.bfloat16), _sds((16, 4), jnp.float32))))
        assert check_dtype_flow(tp) == []

    def test_f64_leak_flagged(self):
        x64 = bool(jax.config.jax_enable_x64)
        try:
            jax.config.update("jax_enable_x64", True)
            closed = jax.make_jaxpr(lambda x: x * 2.0)(
                _sds((4,), jnp.float64))
        finally:
            jax.config.update("jax_enable_x64", x64)
        tp = analysis.TracedProgram(
            site=site_for_fn("t.f64", lambda: None, ()),
            closed=closed, donated_invars=frozenset())
        assert any(fd.rule == "X-F64" for fd in check_dtype_flow(tp))

    def test_waiver_silences_promote(self, tmp_path):
        mod = _mod_from(tmp_path, "bad_promote_waived", (
            "import jax.numpy as jnp\n"
            "def f(x, w):\n"
            "    xf = x.astype(jnp.float32)\n"
            "    return xf @ w"
            "  # tpu-lint: ok(X-PROMOTE) -- test fixture\n"))
        tp = trace_program(site_for_fn(
            "t.waived_promote", mod.f,
            (_sds((8, 16), jnp.bfloat16), _sds((16, 4), jnp.float32)),
            compute_dtype="bfloat16"))
        findings = analysis.run_dtype_pass(traced={"t": tp})
        assert findings and all(fd.waived for fd in findings)


# ---------------------------------------------------------------------
# SYNC: X-SYNC / X-CHURN
# ---------------------------------------------------------------------

_CALLBACK_IN_SCAN = (
    "import jax\n"
    "def f(x):\n"
    "    def body(c, _):\n"
    "        jax.debug.print('c={c}', c=c)WAIVER\n"
    "        return c + 1.0, c\n"
    "    return jax.lax.scan(body, x, None, length=4)\n")


class TestSyncPass:
    def test_callback_in_scan_flagged(self, tmp_path):
        mod = _mod_from(tmp_path, "cb_scan",
                        _CALLBACK_IN_SCAN.replace("WAIVER", ""))
        tp = trace_program(site_for_fn("t.cb", mod.f,
                                       (_sds((), jnp.float32),)))
        assert any(fd.rule == "X-SYNC" for fd in check_host_sync(tp))

    def test_hot_loop_flags_top_level_callback(self):
        def f(x):
            jax.debug.print("x={x}", x=x)
            return x + 1.0

        tp = trace_program(site_for_fn(
            "t.hot", f, (_sds((), jnp.float32),), hot_loop=True))
        assert any(fd.rule == "X-SYNC" for fd in check_host_sync(tp))
        # the same program outside a hot loop is fine (one-shot sync)
        tp2 = trace_program(site_for_fn(
            "t.cold", f, (_sds((), jnp.float32),)))
        assert check_host_sync(tp2) == []

    def test_clean_loop_not_flagged(self):
        def f(x):
            return jax.lax.fori_loop(0, 4, lambda i, c: c + i, x)

        tp = trace_program(site_for_fn(
            "t.clean", f, (_sds((), jnp.int32),), hot_loop=True))
        assert check_host_sync(tp) == []

    def test_unhashable_static_kwargs_flag_churn(self):
        site = site_for_fn("t.churn", lambda x: x, (),
                           static_kwargs={"axes": [1, 2]})
        assert [fd.rule for fd in check_churn(site)] == ["X-CHURN"]
        ok = site_for_fn("t.ok", lambda x: x, (),
                         static_kwargs={"axis": -1, "mode": "full"})
        assert check_churn(ok) == []

    def test_waiver_silences_sync(self, tmp_path):
        mod = _mod_from(tmp_path, "cb_scan_waived",
                        _CALLBACK_IN_SCAN.replace(
                            "WAIVER", "  # tpu-lint: ok(X-SYNC) -- "
                                      "debug fixture"))
        tp = trace_program(site_for_fn("t.cbw", mod.f,
                                       (_sds((), jnp.float32),)))
        findings = analysis.run_sync_pass(traced={"t": tp})
        assert findings and all(fd.waived for fd in findings)


# ---------------------------------------------------------------------
# MEMORY: liveness model + M-HBM + XLA cross-check
# ---------------------------------------------------------------------

class TestMemoryPass:
    def test_known_peak_chain_exact(self):
        """y = x+1; z = y+1 — peak is exactly 3 buffers undonated
        (caller holds x across the whole program), 2 donated."""
        n = 256 * 256 * 4

        def f(x):
            y = x + 1.0
            return y + 1.0

        closed = jax.make_jaxpr(f)(_sds((256, 256), jnp.float32))
        est = peak_live_bytes(closed)
        assert est.peak_bytes == 3 * n
        est_don = peak_live_bytes(closed, donated_invars=frozenset({0}))
        assert est_don.peak_bytes == 2 * n
        assert est.arg_bytes == n and est.out_bytes == n

    def test_loop_body_temp_counted(self):
        """A scan body materializing a [512, 512] outer product must
        surface in the outer peak (inner peak net of boundary)."""
        def f(c):
            def body(c, _):
                t = jnp.outer(c, c)          # 1 MiB f32 temp
                return t.sum(axis=1) * 1e-3, ()
            out, _ = jax.lax.scan(body, c, None, length=3)
            return out

        closed = jax.make_jaxpr(f)(_sds((512,), jnp.float32))
        est = peak_live_bytes(closed)
        assert est.peak_bytes >= 512 * 512 * 4

    def test_m_hbm_fires_on_v5e_fits_on_v5p(self):
        def f(w):
            return (w * 2.0).sum()

        tp = trace_program(site_for_fn(
            "t.oversize", f, (_sds((1 << 33,), jnp.float32),)))
        bad = analysis.run_memory_pass(generation="v5e",
                                       traced={"t": tp})
        assert [fd.rule for fd in bad] == ["M-HBM"]
        assert "v5e" in bad[0].message
        assert analysis.run_memory_pass(generation="v5p",
                                        traced={"t": tp}) == []

    def test_waiver_silences_m_hbm(self, tmp_path):
        mod = _mod_from(tmp_path, "oversize_waived", (
            "def build():"
            "  # tpu-lint: ok(M-HBM) -- known-oversize fixture\n"
            "    import jax, jax.numpy as jnp\n"
            "    fn = lambda w: (w * 2.0).sum()\n"
            "    return fn, (jax.ShapeDtypeStruct((1 << 33,),"
            " jnp.float32),)\n"))
        site = analysis.ProgramSite("t.waived_big", mod.build)
        tp = trace_program(site)
        findings = analysis.run_memory_pass(generation="v5e",
                                            traced={"t": tp})
        assert findings and all(fd.waived for fd in findings)

    def test_decode_estimate_within_20pct_of_xla(self):
        """Acceptance criterion: the static peak-live bound for the
        decode program lands within 20% of the compiled program's own
        memory accounting (CPU backend; both sides undonated so args
        are counted once on each). The f32 program variant is the
        apples-to-apples one here — XLA:CPU emulates bf16 through f32
        temp copies of every weight, which no real TPU run pays."""
        from paddle_tpu.analysis import program_sites as ps

        import re

        fn, args = ps.build_decode_program(cast_bf16=False)
        est = peak_live_bytes(jax.make_jaxpr(fn)(*args))
        compiled = jax.jit(fn).lower(*args).compile()
        ma = compiled.memory_analysis()
        # XLA:CPU re-lays the K/V page pool out for its scatter: one
        # layout-changing copy of each pool PARAMETER on entry — a
        # backend temp the program itself never asks for (the jaxpr
        # keeps the page-major layout), so it is taken off XLA's side.
        # Exactly those two copies, whole and into a non-default
        # layout: the allowance must not quietly absorb another temp.
        pool_k, pool_v = args[-3], args[-2]
        dims = ",".join(str(d) for d in pool_k.shape)
        default = ",".join(str(d) for d in reversed(range(pool_k.ndim)))
        copies = re.findall(
            r"= f32\[([\d,]+)\]\{([\d,]+)\} copy\(%(cache_[kv])[.\d]*\)",
            compiled.as_text())
        assert sorted(c[2] for c in copies) == ["cache_k", "cache_v"], \
            copies
        assert all(c[0] == dims and c[1] != default for c in copies), \
            copies
        relayout = pool_k.nbytes + pool_v.nbytes
        xla = (ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes - relayout)
        assert xla > 0
        ratio = est.peak_bytes / xla
        assert 0.8 <= ratio <= 1.2, (est.peak_bytes, xla, ratio)

    def test_hbm_table_shape(self):
        # the issue-pinned capacities: v4 32G, v5e 16G
        assert dvmem.HBM_BUDGET_BYTES["v4"] == 32 * dvmem.GiB
        assert dvmem.HBM_BUDGET_BYTES["v5e"] == 16 * dvmem.GiB
        assert set(dvmem.HBM_BUDGET_BYTES) == set(dvmem.VMEM_BUDGET_BYTES)
        assert dvmem.hbm_budget_bytes("v5e") == \
            16 * dvmem.GiB - dvmem.HBM_RESERVE_BYTES


# ---------------------------------------------------------------------
# SPMD: S-GATHER / S-MATCH / S-UNSPEC on the virtual mesh
# ---------------------------------------------------------------------

def _gather_build():
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = analysis.virtual_mesh()
    repl = NamedSharding(mesh, P())

    def fn(x):
        return jax.lax.with_sharding_constraint(x * 2.0, repl)

    x = jax.device_put(jnp.ones((8, 8)),
                       NamedSharding(mesh, P("x", None)))
    return fn, (x,)


class TestSpmdPass:
    def test_virtual_mesh_available(self, virtual_devices):
        assert analysis.mesh_available()
        assert analysis.virtual_mesh() is not None

    def test_injected_missing_constraint_all_gather(self):
        """Acceptance criterion: a sharded input forced replicated
        (the dropped-sharding-constraint shape) must flag the GSPMD
        all-gather on the virtual 8-device mesh."""
        site = SpmdSite("t.gather", _gather_build, allowed=frozenset())
        findings = check_spmd_site(site)
        assert [fd.rule for fd in findings] == ["S-GATHER"]
        assert "all-gather" in findings[0].message

    def test_declared_collective_passes(self):
        site = SpmdSite("t.gather_ok", _gather_build,
                        allowed=frozenset({"all-gather"}))
        assert check_spmd_site(site) == []

    def test_asymmetric_branch_collectives_flag_s_match(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = analysis.virtual_mesh()

        def build(asym):
            def body(x):
                def hot(v):
                    return jax.lax.psum(v, "x")

                def cold(v):
                    return v if asym else jax.lax.psum(v, "x") * 0.5
                return jax.lax.cond(x.sum() > 0, hot, cold, x)

            # vma checking off: the asymmetric pair is exactly what a
            # typed cond refuses (psum output is replicated, the
            # pass-through is varying) — the lint must see it anyway
            fn = jax.shard_map(body, mesh=mesh, in_specs=(P("x"),),
                               out_specs=P("x"), check_vma=False)
            x = jax.device_put(jnp.ones((8, 4)),
                               NamedSharding(mesh, P("x", None)))
            return fn, (x,)

        bad = SpmdSite("t.asym", lambda: build(True),
                       allowed=frozenset({"all-reduce"}))
        assert any(fd.rule == "S-MATCH" for fd in check_spmd_site(bad))
        good = SpmdSite("t.sym", lambda: build(False),
                        allowed=frozenset({"all-reduce"}))
        assert check_spmd_site(good) == []

    def test_missing_output_constraint_flags_s_unspec(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = analysis.virtual_mesh()

        def build():
            def fn(x, w):
                return x @ w

            x = jax.device_put(jnp.ones((8, 16)),
                               NamedSharding(mesh, P("x", None)))
            w = jax.device_put(jnp.ones((16, 4)),
                               NamedSharding(mesh, P()))
            return fn, (x, w)

        site = SpmdSite("t.unspec", build,
                        allowed=frozenset({"all-gather", "all-reduce"}),
                        expects_constraint=True)
        assert any(fd.rule == "S-UNSPEC"
                   for fd in check_spmd_site(site))
        # the same program WITH the constraint is clean
        ok = SpmdSite("t.spec", _gather_build,
                      allowed=frozenset({"all-gather"}),
                      expects_constraint=True)
        assert check_spmd_site(ok) == []

    def test_waiver_silences_s_gather(self, tmp_path):
        mod = _mod_from(tmp_path, "gather_waived", (
            "def build():"
            "  # tpu-lint: ok(S-GATHER) -- replication intended\n"
            "    import jax, jax.numpy as jnp\n"
            "    from jax.sharding import NamedSharding,"
            " PartitionSpec as P\n"
            "    from paddle_tpu import analysis\n"
            "    mesh = analysis.virtual_mesh()\n"
            "    repl = NamedSharding(mesh, P())\n"
            "    fn = lambda x: jax.lax.with_sharding_constraint("
            "x * 2.0, repl)\n"
            "    x = jax.device_put(jnp.ones((8, 8)),"
            " NamedSharding(mesh, P('x', None)))\n"
            "    return fn, (x,)\n"))
        site = SpmdSite("t.waived_gather", mod.build,
                        allowed=frozenset())
        findings = analysis.run_spmd_pass(sites=[site])
        assert findings and all(fd.waived for fd in findings)


# ---------------------------------------------------------------------
# preflight gate + ratchet + bench_gate wiring
# ---------------------------------------------------------------------

class TestPreflightGate:
    def test_refuses_on_unwaivered_findings(self, monkeypatch, capsys):
        from paddle_tpu.analysis import preflight as pf

        monkeypatch.setattr(
            analysis, "run_all_passes",
            lambda generation=None: {"t": [analysis.Finding(
                rule="T-BAD", message="injected")]})
        with pytest.raises(SystemExit) as ei:
            pf.preflight("t_tool")
        assert ei.value.code == 2
        assert "REFUSING" in capsys.readouterr().err

    def test_no_lint_and_env_escape_hatches(self, monkeypatch):
        from paddle_tpu.analysis import preflight as pf

        boom = lambda generation=None: (_ for _ in ()).throw(
            AssertionError("lint ran"))
        monkeypatch.setattr(analysis, "run_all_passes", boom)
        pf.preflight("t_tool", no_lint=True)     # flag skips
        monkeypatch.setenv("PADDLE_TPU_NO_LINT", "1")
        pf.preflight("t_tool")                   # env skips

    def test_publish_lint_stats_counters(self):
        from paddle_tpu.analysis.preflight import publish_lint_stats
        from paddle_tpu.profiler import stats

        before_f = stats.counter("lint.findings").value
        before_w = stats.counter("lint.waived").value
        publish_lint_stats({"t": [
            analysis.Finding(rule="A", message="m"),
            analysis.Finding(rule="B", message="m", waived=True,
                             waive_reason="r")]})
        assert stats.counter("lint.findings").value == before_f + 1
        assert stats.counter("lint.waived").value == before_w + 1
        # gauges mirror the per-run state so a CLEAN run (counter value
        # 0, filtered from snapshots) still materializes in telemetry
        assert stats.gauge("lint.findings").value == 1
        assert stats.gauge("lint.waived").value == 1
        publish_lint_stats({"t": []})
        assert stats.gauge("lint.findings").value == 0
        assert "lint.findings" in stats.snapshot()["gauges"]

    def test_bench_and_profile_tools_wired(self):
        """The chip-time entry points all run the preflight gate and
        expose the --no-lint escape hatch."""
        for rel in ("bench.py", "tools/bert_profile.py",
                    "tools/train_profile.py"):
            src = open(os.path.join(REPO, rel), encoding="utf-8").read()
            assert "preflight(" in src, rel
            assert "--no-lint" in src or "no_lint" in src, rel


class TestRatchet:
    def test_rule_counts_exclude_waived(self):
        results = {"p": [
            analysis.Finding(rule="X-SYNC", message="m"),
            analysis.Finding(rule="X-SYNC", message="m"),
            analysis.Finding(rule="M-HBM", message="m", waived=True,
                             waive_reason="legacy")]}
        assert analysis.rule_counts(results) == {"X-SYNC": 2}

    def test_ratchet_only_tightens(self):
        base = {"X-SYNC": 2, "M-HBM": 1}
        # equal or fewer: clean, even though findings exist (legacy)
        assert analysis.ratchet({"X-SYNC": 2}, base) == []
        assert analysis.ratchet({"X-SYNC": 1, "M-HBM": 1}, base) == []
        # any growth (or a new rule) fails
        assert analysis.ratchet({"X-SYNC": 3}, base)
        assert analysis.ratchet({"S-GATHER": 1}, base)

    def test_cli_baseline_parser_accepts_both_formats(self):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import tpu_lint
        finally:
            sys.path.pop(0)
        assert tpu_lint._baseline_counts(
            {"rule_counts": {"X-SYNC": 2}}) == {"X-SYNC": 2}
        report = {"passes": {"sync": [
            {"rule": "X-SYNC", "waived": False},
            {"rule": "X-SYNC", "waived": True}]}}
        assert tpu_lint._baseline_counts(report) == {"X-SYNC": 1}
        assert tpu_lint.SCHEMA_VERSION == 2


class TestBenchGateLintMetric:
    def test_lint_findings_gate_direction_up(self):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import bench_gate
        finally:
            sys.path.pop(0)
        assert bench_gate.DEFAULT_METRICS["lint_findings"] == "up"
        assert bench_gate.DEFAULT_METRICS["lint.findings"] == "up"
        prev = {"lint_findings": 0,
                "telemetry": {"counters": {"lint.findings": 0}}}
        worse = {"lint_findings": 5,
                 "telemetry": {"counters": {"lint.findings": 5}}}
        bad, n = bench_gate.gate(prev, worse)
        assert n >= 2 and bad
        assert any("lint" in b for b in bad)
        # improvement (fewer findings) must NOT trip the gate
        bad2, _ = bench_gate.gate(worse, prev)
        assert not bad2

    def test_single_new_finding_trips_no_floor(self):
        """ANY lint growth regresses — the count noise floor (3) that
        protects cache counters must not swallow 0 -> 1 findings, and a
        clean run records lint state as a GAUGE (zero counters are
        snapshot-filtered) so the comparison actually happens."""
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import bench_gate
        finally:
            sys.path.pop(0)
        clean = {"telemetry": {"gauges": {"lint.findings": 0}}}
        one = {"telemetry": {"counters": {"lint.findings": 1},
                             "gauges": {"lint.findings": 1}}}
        bad, n = bench_gate.gate(clean, one)
        assert n and bad, (bad, n)
