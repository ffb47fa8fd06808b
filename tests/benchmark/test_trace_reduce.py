"""The reduction from a trace to numbers, on a synthetic trace shaped like
``jax.profiler.ProfileData``; and the operation and byte counts against hand
counts for the GPT-3 1.3B shapes."""
import json
import os
from types import SimpleNamespace as NS

import pytest

import bench_toy  # noqa: F401  (puts the repo on sys.path)
from benchmark import trace_reduce as tr
from benchmark.kernels import gpt

MS = 1_000_000


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS,
              stats=[])


def synthetic():
    """Window 0..100 ms. Device busy 10-30 (two ops back to back, one
    nested program), 50-60, and an op that straddles the window's end
    95-105: busy 20 + 10 + 5 = 35 ms, idle 65 ms."""
    ops = [ev("fusion.1", 10, 10), ev("custom-call.7", 20, 10),
           ev("fusion.2", 50, 10), ev("fusion.1", 95, 10),
           ev("fusion.9", 200, 10)]                       # outside
    mods = [ev("jit_decode(1)", 10, 20), ev("jit_prefill(2)", 50, 10),
            ev("jit_decode(1)", 95, 10)]
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=ops), NS(name="XLA Modules", events=mods)])
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        ev("bench.traced_window", 0, 100),
        ev("bench.engine.step", 0, 45),       # covers gaps 0-10 and 30-45
        ev("bench.idle_wait", 60, 30),        # covers gap 60-90
        ev("other", 0, 100)]),
        NS(name="gen", events=[ev("bench.submit", 40, 4)])])   # nested 40-44
    return NS(planes=[host, dev])


def test_union_and_gaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.gaps([(0, 3), (5, 8)], 0, 10) == [(3, 5), (8, 10)]
    assert tr.gaps([], 2, 4) == [(2, 4)]
    assert tr.total(tr.clip([(0, 10)], 5, 20)) == 5


def test_busy_idle_and_names():
    s = tr.reduce(synthetic(), n_devices=1)
    assert s.window_s == pytest.approx(0.100)
    assert s.busy_s == pytest.approx(0.035)
    assert s.idle_share() == pytest.approx(0.65)
    assert s.op_s["fusion.1"] == pytest.approx(0.020)     # self time, whole
    assert s.op_n["fusion.1"] == 2
    assert "fusion.9" not in s.op_s
    assert s.modules_matching("decode") == (pytest.approx(0.025), 2)
    assert s.ops_matching(r"^custom-call") == (pytest.approx(0.010), 1)
    assert s.top_ops(1) == [["fusion", pytest.approx(0.030)]]


def test_gap_attribution():
    s = tr.reduce(synthetic(), n_devices=1)
    # gaps: 0-10, 30-50, 60-95. engine.step covers 0-10 and 30-45 but the
    # nested submit span (40-44) takes its own 4 ms; idle_wait 60-90
    assert s.gap_s["engine.step"] == pytest.approx(0.010 + 0.015 - 0.004)
    assert s.gap_s["submit"] == pytest.approx(0.004)
    assert s.gap_s["idle_wait"] == pytest.approx(0.030)
    assert s.gap_s["unattributed"] == pytest.approx(0.005 + 0.005)
    assert sum(s.gap_s.values()) == pytest.approx(s.window_s - s.busy_s)
    assert s.top_gaps(1)[0][0] == "idle_wait"


def test_no_device_plane_is_an_error():
    data = synthetic()
    data.planes = [p for p in data.planes if p.name.startswith("/host")]
    with pytest.raises(ValueError, match="no device plane"):
        tr.reduce(data)


GPT13 = {"d_model": 2048, "n_layers": 24, "n_heads": 16, "d_ff": 8192,
         "vocab_size": 51200}


def test_gpt_counts_by_hand():
    # one layer: qkv 2048*6144 + out 2048*2048 + ffn 2 * 2048*8192
    assert gpt.layer_matmul_params(GPT13) == 12582912 + 4194304 + 33554432
    assert gpt.stack_matmul_params(GPT13) == 1207959552
    assert gpt.head_params(GPT13) == 104857600
    # K and V, 24 layers, 2048 wide, bf16
    assert gpt.kv_bytes_per_token(GPT13) == 196608
    # training at s2048: 6 per matmul weight (the untied head is one), and
    # 3 x the causal forward attention 4*d*L*(s+1)/2 per token. The token
    # and position tables (51200 x 2048 and 2048 x 2048) are looked up and
    # not counted: 6 * 109,051,904 = 0.65 GFLOP a token that bench.py added.
    assert gpt.train_flops_per_token(GPT13, 2048) == \
        6 * (1207959552 + 104857600) + 3 * 4 * 2048 * 24 * 2049 // 2
    # a decode step of two sequences holding 100 and 300 tokens
    assert gpt.decode_step_flops(GPT13, [100, 300]) == \
        2 * 2 * (1207959552 + 104857600) + 4 * 2048 * 24 * (101 + 301)
    assert gpt.decode_step_bytes(GPT13, [100, 300]) == \
        2 * (1207959552 + 104857600) + 400 * 196608
    # a final prefill chunk of 256 tokens after 512 cached ones
    pairs = 256 * 512 + 256 * 257 // 2
    assert gpt.causal_pairs(512, 256) == pairs
    assert gpt.prefill_chunk_flops(GPT13, 512, 256, True) == \
        2 * 1207959552 * 256 + 4 * 2048 * 24 * pairs + 2 * 104857600


def test_configuration_file_matches_the_counts():
    with open(os.path.join(bench_toy.REPO, "benchmark", "configs",
                           "gpt3-1.3b.json")) as f:
        cfg = json.load(f)
    assert {k: cfg[k] for k in GPT13} == GPT13
    assert cfg["reduced"] == [] and cfg["source"] and cfg["assumed"]
    with open(os.path.join(bench_toy.REPO, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    row = peaks["chips"]["tpu v5 lite"]
    assert (row["bf16_flops_per_s"], row["hbm_bytes_per_s"],
            row["hbm_bytes"]) == (197e12, 819e9, 16e9)
    assert peaks["source"]


def test_names_and_self_time():
    line = ("%copy.44.remat = bf16[39936,16,16,128]{3,2,1,0:T(8,128)(2,1)} "
            "copy(bf16[39936,16,16,128]{3,1,2,0:T(8,128)(2,1)} %fusion.140)")
    assert tr.short_name(line) == "copy.44.remat bf16[39936,16,16,128]"
    assert tr.base_name(tr.short_name(line)) == \
        "copy.remat bf16[39936,16,16,128]"
    assert tr.short_name("fusion.3") == "fusion.3"
    # a while that holds two operations, one of which holds a third
    got = dict(tr.self_times([("while", 0, 100), ("a", 10, 20),
                              ("b", 40, 10), ("c", 42, 3)]))
    assert got == {"while": 70, "a": 20, "b": 7, "c": 3}


def test_attention_backward_roofline_reader():
    """24 steps of batch 2 x 2048 whose backward kernels took 0.8 s: the
    needed 2 x 4*d*L*pairs operations a row over the v5e peak, by hand."""
    from benchmark import harness

    s = tr.TraceSummary()
    s.window_s = 8.0
    s.module_s["jit__pure_step(1)"], s.module_n["jit__pure_step(1)"] = 7.9, 24
    for name, sec in (("flash_mha_bwd_dq_block_q_major_256 bf16[2]", 0.41),
                      ("flash_mha_bwd_dkv_block_q_major_256 (bf16[2]", 0.39),
                      ("closed_call.3 bf16[2,16,2048,128]", 0.2)):
        s.op_s[name], s.op_n[name] = sec, 24 * 24
    ctx = {"trace": s, "config": GPT13,
           "peaks": {"bf16_flops_per_s": 197e12},
           "facts": {"tokens_per_step": 4096, "seq": 2048}}
    read = harness.load_module(
        os.path.join(bench_toy.REPO, "benchmark", "metrics",
                     "train_attn_bwd_roofline.py"), "m_attn_bwd").read
    pairs = 2048 * 2049 // 2
    want = 100 * (2 * 4 * 2048 * 24 * pairs * 2 * 24 / 197e12) / 0.80
    assert read(ctx) == pytest.approx(want)
    assert 20 < want < 30
    del s.op_s["flash_mha_bwd_dq_block_q_major_256 bf16[2]"]
    del s.op_s["flash_mha_bwd_dkv_block_q_major_256 (bf16[2]"]
    assert read(ctx) is None          # nothing to read: left out, never 0
