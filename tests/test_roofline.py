"""Device-level observability: roofline math, HBM telemetry, fleet merge.

Covers PR-2's device observability layer: XLA cost-model extraction
(``compiled.cost_analysis()`` → flops/bytes), achieved-rate /
MFU / bandwidth-utilization arithmetic against the (env-overridable)
peak table, auto-recording from the jit layers, HBM memory sampling,
and the trace_merge fold (rank traces → one timeline; rank snapshots →
one fleet snapshot)."""
import json
import os
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.profiler import memory, roofline, stats

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_trace_merge():
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        import trace_merge
    finally:
        sys.path.pop(0)
    return trace_merge


@pytest.fixture(autouse=True)
def _fresh():
    stats.enable()
    stats.reset()
    roofline.reset()
    yield
    roofline.reset()


class TestCostModel:
    def test_matmul_flops_matches_2mnk(self):
        """XLA's CPU cost model reports a matmul as exactly 2*M*N*K
        flops — the analytic anchor the whole roofline rests on."""
        M, K, N = 64, 128, 32
        f = jax.jit(lambda a, b: a @ b)
        compiled = f.lower(jnp.ones((M, K)), jnp.ones((K, N))).compile()
        cost = roofline.program_cost(compiled)
        assert cost is not None
        assert cost["flops"] == pytest.approx(2 * M * N * K, rel=1e-6)
        # bytes accessed covers at least the operands + the result
        min_bytes = 4 * (M * K + K * N + M * N)
        assert cost["bytes"] >= min_bytes

    def test_record_program_sets_compile_gauges(self):
        f = jax.jit(lambda a: a * 2.0)
        compiled = f.lower(jnp.ones((16, 16))).compile()
        cost = roofline.record_program("t.prog", compiled)
        assert cost["flops"] > 0
        assert stats.gauge("compile.flops").value == cost["flops"]
        assert stats.gauge("compile.bytes").value == cost["bytes"]
        assert "t.prog" in roofline.report()

    def test_analyze_computes_rates_from_cost(self, monkeypatch):
        """MFU and bandwidth utilization are DERIVED from the recorded
        cost + wall time + peak table — pin the peaks via env and check
        the arithmetic end to end."""
        monkeypatch.setenv(roofline.ENV_PEAK_FLOPS, "1e12")
        monkeypatch.setenv(roofline.ENV_PEAK_HBM_BW, "1e11")
        roofline.record_program("t.prog", flops=2e9, bytes_accessed=4e8)
        res = roofline.analyze("t.prog", wall_s=1e-3)
        assert res.achieved_flops_per_s == pytest.approx(2e12)
        assert res.achieved_bytes_per_s == pytest.approx(4e11)
        assert res.mfu == pytest.approx(2.0)       # 2e12 / 1e12
        assert res.bw_util == pytest.approx(4.0)   # 4e11 / 1e11
        # gauges published for the stats snapshot / chrome counters
        assert stats.gauge("roofline.mfu").value == pytest.approx(2.0)
        assert stats.gauge("roofline.bw_util").value == pytest.approx(4.0)
        # the formatted line carries the four figures
        line = res.format()
        assert "MFU" in line and "GB/s" in line

    def test_analyze_unknown_program_returns_none(self):
        assert roofline.analyze("t.nope", 1.0) is None
        assert roofline.analyze("t.nope", 0.0) is None

    def test_device_peaks_env_override(self, monkeypatch):
        monkeypatch.setenv(roofline.ENV_PEAK_FLOPS, "5e12")
        monkeypatch.setenv(roofline.ENV_PEAK_HBM_BW, "7e11")
        assert roofline.device_peaks() == (5e12, 7e11)

    def test_device_peaks_unknown_device_is_an_error(self):
        """No made-up peak for a device that is not in the chip table:
        the CPU test host included."""
        with pytest.raises(ValueError, match="not in"):
            roofline.device_peaks(jax.devices()[0])

    def test_device_peaks_come_from_the_chip_table(self):
        from paddle_tpu.device import chip

        class V5e:
            platform, device_kind = "tpu", "TPU v5 lite"

        assert roofline.device_peaks(V5e()) == (197e12, 819e9)
        assert chip.chip_spec("TPU v5 lite").generation == "v5e"
        with pytest.raises(ValueError):
            chip.chip_spec("TPU v99")

    def test_analyze_off_tpu_keeps_rates_without_utilization(self):
        roofline.record_program("t.cpu", flops=2e9, bytes_accessed=4e8)
        res = roofline.analyze("t.cpu", wall_s=1e-3)
        assert res.achieved_flops_per_s == pytest.approx(2e12)
        assert res.mfu is None and res.bw_util is None
        assert "not measured" in res.format()
        assert roofline.report()["t.cpu"]["mfu"] is None


class TestJitLayerAutoRecording:
    def test_to_static_records_cost_and_roofline(self):
        M = 32

        @paddle.jit.to_static
        def f(x):
            return x @ x

        x = paddle.to_tensor(np.ones((M, M), np.float32))
        t0 = time.perf_counter()
        np.asarray(f(x).numpy())         # the fetch syncs the call
        wall = time.perf_counter() - t0
        rep = roofline.report()
        assert "to_static[f]" in rep
        # the matmul dominates: flops ≈ 2*M^3 (XLA may fold a few
        # elementwise ops on top)
        assert rep["to_static[f]"]["flops"] >= 2 * M ** 3
        assert stats.gauge("compile.flops").value > 0
        # a bare call records cost only: no rate from an enqueue time
        assert "mfu" not in rep["to_static[f]"]
        # the caller brings a synced wall time → rates present
        res = roofline.analyze("to_static[f]", wall)
        assert res.achieved_flops_per_s == pytest.approx(
            rep["to_static[f]"]["flops"] / wall)
        assert "mfu" in roofline.report()["to_static[f]"]

    def test_train_step_roofline(self):
        import paddle_tpu.nn as nn

        model = nn.Linear(8, 4)
        opt = paddle.optimizer.SGD(0.1, parameters=model.parameters())
        step = paddle.jit.TrainStep(
            model, lambda out, lbl: ((out - lbl) ** 2).mean(), opt)
        inp = paddle.to_tensor(np.ones((2, 8), np.float32))
        lbl = paddle.to_tensor(np.zeros((2, 4), np.float32))
        step([inp], [lbl])
        res = step.roofline(1e-3)
        assert res is not None
        assert res.flops > 0 and res.bytes > 0
        assert res.achieved_flops_per_s == pytest.approx(
            res.flops / 1e-3)

    def test_decode_engine_records_decode_cost(self):
        from paddle_tpu.inference import FusedCausalLM, GenerationEngine

        paddle.seed(0)
        lm = FusedCausalLM(vocab_size=32, embed_dim=16, num_heads=2,
                           dim_feedforward=32, num_layers=1,
                           max_position=64)
        eng = GenerationEngine(lm, page_size=4, max_length=32,
                               decode_chunk=4)
        out = eng.generate(np.zeros((2, 4), np.int64), max_new_tokens=8)
        assert out.shape == (2, 12)
        rep = roofline.report()
        assert "prefill" in rep
        # grouped weight-stream decode (the r6 default) reports under
        # decode.<dtype>_grouped[k=*]; ungrouped under decode[k=*]
        decode_names = [n for n in rep if n.startswith("decode")
                        and "[k=" in n]
        assert decode_names
        # the decode chunk was analyzed against an honestly synced wall
        # time, so achieved rates are present
        assert all("bw_util" in rep[n] for n in decode_names)


class TestMemoryTelemetry:
    def test_sample_smoke(self):
        x = paddle.to_tensor(np.ones((128, 128), np.float32))  # noqa: F841
        out = memory.sample()
        # CPU PJRT exposes no allocator counters — keys exist, zeros ok
        assert set(out) >= {"bytes_in_use", "peak_bytes_in_use",
                            "bytes_limit"}
        # ...but the live-array census always works
        assert out["live"]["count"] >= 1
        assert out["live"]["bytes"] >= 128 * 128 * 4
        assert stats.gauge("hbm.live_buffers").value >= 1
        assert stats.gauge("hbm.live_bytes").value >= 128 * 128 * 4
        assert "float32" in out["live"]["by_dtype"]
        assert out["live"]["top_shapes"]
        # JSON-able end to end (rides snapshots into BENCH files)
        json.dumps(out)

    def test_watermark_falls_back_to_census_on_cpu(self):
        x = paddle.to_tensor(np.ones((64,), np.float32))  # noqa: F841
        wm = memory.watermark()
        assert wm is not None
        assert wm["source"] in ("pjrt", "live_arrays")
        assert wm["bytes_in_use"] > 0

    def test_profiler_samples_hbm_gauges(self):
        from paddle_tpu.profiler import Profiler

        a = paddle.to_tensor(np.ones((32, 32), np.float32))
        with Profiler(on_trace_ready=lambda p: None) as prof:
            _ = a @ a
            prof.step()
        hbm_events = [e for e in prof._events
                      if e.get("ph") == "C"
                      and e["name"].startswith("hbm.")]
        assert hbm_events, "no hbm.* counter events sampled"


class TestTraceMerge:
    def _synthetic_rank(self, tmp_path, rank, pid):
        trace = {
            "traceEvents": [
                {"name": "op::matmul", "ph": "X", "pid": pid,
                 "tid": 1, "ts": 10.0 * rank, "dur": 5.0,
                 "cat": "host"},
                {"name": "op.matmul", "ph": "C", "pid": pid, "tid": 0,
                 "ts": 1.0, "cat": "counter",
                 "args": {"value": rank + 1}},
            ],
            "displayTimeUnit": "ms",
            "metadata": {"process_index": rank, "pid": pid},
        }
        snap = {
            "meta": {"process_index": rank, "process_count": 2,
                     "pid": pid},
            "counters": {"dist.all_reduce.calls": 3 + rank,
                         "op.matmul": 10 * (rank + 1)},
            "gauges": {"dist.process_index": rank,
                       "hbm.bytes_in_use": 100.0 * (rank + 1)},
            "histograms": {"compile.vjp_trace_us": {
                "count": 2, "total": 30.0 * (rank + 1),
                "avg": 15.0 * (rank + 1),
                "min": 10.0 * (rank + 1), "max": 20.0 * (rank + 1),
                "p50": 15.0, "p90": 20.0, "p99": 20.0,
                "buckets": [[16.0, 1], [32.0, 1]],
            }},
        }
        (tmp_path / f"trace_rank{rank}.json").write_text(
            json.dumps(trace))
        (tmp_path / f"stats_rank{rank}.json").write_text(
            json.dumps(snap))

    def test_round_trip_two_ranks(self, tmp_path):
        """Synthetic 2-rank run dir → one merged timeline + one folded
        fleet snapshot with sum/max/bucket-fold semantics."""
        trace_merge = _load_trace_merge()
        # both ranks landed the SAME host pid — the collision the
        # rank-stamping exists to disambiguate
        self._synthetic_rank(tmp_path, 0, pid=4242)
        self._synthetic_rank(tmp_path, 1, pid=4242)

        rc = trace_merge.main([str(tmp_path)])
        assert rc == 0

        merged = json.load(open(tmp_path / "merged_trace.json"))
        pids = {e["pid"] for e in merged["traceEvents"]}
        assert pids == {0, 1}
        assert merged["metadata"]["ranks"] == [0, 1]
        spans = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
        assert {e["pid"] for e in spans} == {0, 1}

        fleet = json.load(open(tmp_path / "fleet_stats.json"))
        assert fleet["counters"]["dist.all_reduce.calls"] == 7  # 3 + 4
        assert fleet["counters"]["op.matmul"] == 30
        assert fleet["gauges"]["dist.process_index"] == 1        # max
        assert fleet["gauges"]["hbm.bytes_in_use"] == 200.0      # max
        h = fleet["histograms"]["compile.vjp_trace_us"]
        assert h["count"] == 4
        assert h["total"] == pytest.approx(90.0)
        assert h["min"] == 10.0 and h["max"] == 40.0
        assert h["buckets"] == [[16.0, 2], [32.0, 2]]
        assert h["p50"] is not None and h["p99"] is not None
        assert h["min"] <= h["p50"] <= h["p90"] <= h["p99"] <= h["max"]

    def test_missing_dir_is_an_error(self, tmp_path):
        trace_merge = _load_trace_merge()
        assert trace_merge.main([str(tmp_path / "empty")]) == 2


class TestBenchGate:
    def _doc(self, hit_rate, jit_trace, mfu):
        return {"metric": "x", "telemetry": {
            "counters": {"jit.trace": jit_trace},
            "gauges": {"roofline.mfu": mfu},
            "histograms": {},
            "vjp_cache_hit_rate": hit_rate,
        }}

    def test_pass_and_fail_directions(self, tmp_path):
        sys.path.insert(0, os.path.join(_REPO, "tools"))
        try:
            import bench_gate
        finally:
            sys.path.pop(0)
        prev = self._doc(hit_rate=0.95, jit_trace=10, mfu=0.50)
        same = self._doc(hit_rate=0.95, jit_trace=10, mfu=0.52)
        bad, n = bench_gate.gate(prev, same)
        assert n >= 3 and bad == []
        # retrace storm: jit.trace regresses UP
        storm = self._doc(hit_rate=0.95, jit_trace=40, mfu=0.50)
        bad, _ = bench_gate.gate(prev, storm)
        assert any("jit.trace" in b for b in bad)
        # utilization collapse: mfu regresses DOWN
        slow = self._doc(hit_rate=0.95, jit_trace=10, mfu=0.20)
        bad, _ = bench_gate.gate(prev, slow)
        assert any("roofline.mfu" in b for b in bad)
        # hit-rate collapse
        cold = self._doc(hit_rate=0.40, jit_trace=10, mfu=0.50)
        bad, _ = bench_gate.gate(prev, cold)
        assert any("vjp_cache_hit_rate" in b for b in bad)

    def test_root_scalar_serving_rungs_gate(self):
        """decode_*_tokens_per_sec / *_pct_of_hbm_roofline live at the
        bench JSON root (no telemetry block) — the gate must still
        catch a throughput collapse there, direction 'down'."""
        sys.path.insert(0, os.path.join(_REPO, "tools"))
        try:
            import bench_gate
        finally:
            sys.path.pop(0)
        prev = {"decode_a8w8_tokens_per_sec": 5000.0,
                "decode_a8w8_pct_of_hbm_roofline": 52.0}
        ok = {"decode_a8w8_tokens_per_sec": 5100.0,
              "decode_a8w8_pct_of_hbm_roofline": 53.0}
        bad_doc = {"decode_a8w8_tokens_per_sec": 3000.0,
                   "decode_a8w8_pct_of_hbm_roofline": 30.0}
        bad, n = bench_gate.gate(prev, ok)
        assert n >= 2 and bad == []
        bad, _ = bench_gate.gate(prev, bad_doc)
        assert any("decode_a8w8_tokens_per_sec" in b for b in bad)
        assert any("decode_a8w8_pct_of_hbm_roofline" in b for b in bad)
        # a FASTER run must not trip the 'down' gate
        bad, _ = bench_gate.gate(bad_doc, prev)
        assert bad == []

    def test_cli_round_trip(self, tmp_path):
        sys.path.insert(0, os.path.join(_REPO, "tools"))
        try:
            import bench_gate
        finally:
            sys.path.pop(0)
        a = tmp_path / "a.json"
        b = tmp_path / "b.json"
        a.write_text(json.dumps(self._doc(0.9, 10, 0.5)))
        b.write_text(json.dumps(self._doc(0.9, 11, 0.5)))
        assert bench_gate.main([str(a), str(b)]) == 0
        b.write_text(json.dumps(self._doc(0.9, 100, 0.5)))
        assert bench_gate.main([str(a), str(b)]) == 1
