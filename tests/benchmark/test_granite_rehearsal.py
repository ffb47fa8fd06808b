"""``granite-4.0-h-small.rag-saturated`` rehearsed on the CPU at toy widths
through the code path a chip run takes (``harness.run_cell``, the look for a
chip skipped), beside the three cells of ``test_harness_rehearsal.py``; the
real cell's files as ``BENCHMARK.json`` names them; and each ``.rag`` reader
against a hand-built trace summary and hand-built facts: the number a hand
count gives, and None where its kernel, program or counter is not there."""
import io
import json
import os

import numpy as np
import pytest

import bench_toy
import granite_toy
from benchmark import harness
from benchmark.traffic_gen import Req, closed_loop, quantile_lengths
from benchmark.trace_reduce import TraceSummary

BF16, HBM = 197e12, 819e9
CELL = "granite-4.0-h-small.rag-saturated"
RAG = ["decode_step_ms.rag", "decode_step_roofline.rag",
       "decode_step_mfu.rag", "prefill_chunk_ms.rag",
       "prefill_step_mfu.rag", "ssm_decode_roofline.rag",
       "ssd_prefill_roofline.rag", "moe_experts_roofline.rag",
       "experts_hit_share.rag", "picks_here_share.rag", "step_host_ms.rag",
       "device_idle.rag", "window_compiles.rag", "kv_walked_share.rag",
       "units_live_share.rag"]


# ------------------------------------------------------ the real files

def test_the_cell_as_benchmark_json_names_it():
    cell = harness.Cell(bench_toy.REPO, CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "serve"
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    assert [m["name"] for m in cell.per_layer] == RAG
    assert all(m["moves"] == "serve_tok_s" and m["workloads"] == [CELL]
               for m in cell.per_layer)
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]).read)
    cfg = cell.config
    assert cfg["family"] == "granite_hybrid"
    for f in ("models", "reference", "kernels"):
        assert os.path.exists(os.path.join(cell.bench_dir, f,
                                           "granite_hybrid.py"))
    # the published widths, unchanged
    assert (cfg["hidden_size"], cfg["mamba_n_heads"], cfg["mamba_d_head"],
            cfg["mamba_d_state"], cfg["mamba_d_conv"],
            cfg["mamba_chunk_size"]) == (4096, 128, 64, 128, 4, 256)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["intermediate_size"], cfg["num_experts_per_tok"],
            cfg["router_width"], cfg["shared_intermediate_size"]) \
        == (32, 8, 768, 10, 72, 1536)
    assert (cfg["attention_multiplier"], cfg["embedding_multiplier"],
            cfg["residual_multiplier"], cfg["logits_scaling"]) \
        == (0.0078125, 12, 0.22, 16)
    # the cut, with the published counts beside it
    entry = next(c for c in bench_toy.real_benchmark()["configs"]
                 if c["name"] == "granite-4.0-h-small")
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) \
        == ["num_hidden_layers", "num_local_experts", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_local_experts"],
            cfg["vocab_size"]) == (10, 36, 50176)
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "num_local_experts": 72,
                                "vocab_size": 100352}
    assert cfg["layer_types"][:10].count("attention") == 1
    assert cfg["serving"]["slo"]["prefix_cache"] is False


def test_the_mix_is_the_same_work_for_every_seed():
    mix = harness.Cell(bench_toy.REPO, CELL).traffic
    lens = quantile_lengths(mix["prompt_len"], 64)
    assert lens.min() >= 32 and lens.max() <= 4096
    assert 250 < lens[0] < 350 and 3000 < lens[-1] < 3700
    a = closed_loop(mix, 50176, seed=1)
    b = closed_loop(mix, 50176, seed=2 ** 31 + 5)
    assert len(a) == 64 and len(a[0]) == 12
    size = lambda cl: sorted((len(r.prompt), r.n_out)      # noqa: E731
                             for seq in cl for r in seq[1:])
    assert size(a) == size(b)
    assert not np.array_equal(a[0][1].prompt, b[0][1].prompt)
    assert max(len(r.prompt) + r.n_out for seq in a for r in seq) \
        <= harness.Cell(bench_toy.REPO, CELL).config[
            "serving"]["engine"]["max_length"]


def test_the_kernels_arithmetic_at_the_published_widths():
    """The issue's own arithmetic: 4,757 M parameters held, 8.4 MB of state
    a sequence and layer, 4 KB of K+V a token."""
    cell = harness.Cell(bench_toy.REPO, CELL)
    gh = harness.load_module(os.path.join(cell.bench_dir, "kernels",
                                          "granite_hybrid.py"), "gh_test")
    cfg = cell.config
    assert gh.mamba_params(cfg) == 4096 * 16768 + 8192 * 4096
    assert gh.attention_params(cfg) == 4096 * 6144 + 4096 * 4096
    assert gh.expert_params(cfg) == 9_437_184
    held = gh.dense_params(cfg) + 10 * 36 * gh.expert_params(cfg) \
        + gh.head_params(cfg)
    assert abs(held - 4.757e9) < 0.01e9
    assert gh.weight_bytes(cfg) == 2 * held + 2 * 10 * gh.router_params(cfg)
    assert gh.ssm_decode_bytes(cfg, 1) // 9 \
        == 2 * (128 * 8192 * 4 + 3 * 8448 * 2)
    assert gh.kv_bytes_per_token(cfg) == 4096
    assert gh.moe_stream_bytes(cfg) == 10 * 36 * 9_437_184 * 2


# -------------------------------------------------------- the rehearsal

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return granite_toy.make_root(tmp_path_factory.mktemp("granite"))


def run(root, trace, seed=2 ** 31 + 3):
    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell(root, granite_toy.CELL, seed, 2.0, trace,
                           need_chip=False, out=out, err=err)
    last = json.loads(out.getvalue().strip().splitlines()[-1])
    assert last == json.loads(json.dumps(res))
    return last, err.getvalue()


def test_end_to_end_line(root):
    last, err = run(root, trace=False)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"serve_tok_s", "setup_s"}
    assert last["metrics"]["serve_tok_s"]["value"] > 0
    c = last["compared"]
    assert c["served_token_gap"]["value"] <= c["served_token_gap"]["limit"]
    assert c["tokens_checked"]["value"] >= 1
    assert c["requests_unserved"]["value"] == 0
    assert "memory program=decode.hybrid[k=4]" in err
    assert "memory program=serve.prefill[c=32]" in err


def test_traced_line_has_what_needs_no_device(root, monkeypatch):
    monkeypatch.setattr(harness.Tracer, "summary", lambda self, n: None)
    last, _ = run(root, trace=True)
    m = last["metrics"]
    assert {"experts_hit_share.rag", "picks_here_share.rag",
            "step_host_ms.rag", "window_compiles.rag"} <= set(m)
    assert 0 < m["picks_here_share.rag"]["value"] <= 100
    assert 0 < m["experts_hit_share.rag"]["value"] <= 100
    assert "serve_tok_s" not in m


def test_altered_tokens_come_out_not_correct(root, monkeypatch):
    """The timed path broken underneath: both programs' greedy pick returns
    the neighbour of the best token. (What a state that is never reset or a
    padded row that advances it reads is a chip-size matter: PERF.md.)"""
    import jax.numpy as jnp

    from paddle_tpu.inference.engine import GenerationEngine

    good = GenerationEngine._argmax

    def off_by_one(logits):
        return (good(logits) + 1) % jnp.int32(logits.shape[-1])

    monkeypatch.setattr(GenerationEngine, "_argmax",
                        staticmethod(off_by_one))
    last, _ = run(root, trace=False)
    c = last["compared"]["served_token_gap"]
    assert last["correct"] is False and c["value"] > c["limit"]


# ----------------------------------------------------------- the readers

def reader(name):  # each reader through the harness, by name
    return harness.load_module(
        os.path.join(bench_toy.REPO, "benchmark", "metrics", name + ".py"),
        "rag_reader_" + name.replace(".", "_"))


def config():
    return harness.load_json(os.path.join(
        bench_toy.REPO, "benchmark", "configs", "granite-4.0-h-small.json"))


def kernels():
    return harness.load_module(os.path.join(
        bench_toy.REPO, "benchmark", "kernels", "granite_hybrid.py"),
        "gh_readers")


def summary(ops=(), modules=()):
    s = TraceSummary()
    for name, seconds, n in ops:
        s.op_s[name], s.op_n[name] = seconds, n
    for name, seconds, n in modules:
        s.module_s[name], s.module_n[name] = seconds, n
    return s


def counters(picks, here, hit, held, walked=1860, live=930):
    c = {"serving.moe.picks": picks, "serving.moe.picks_here": here,
         "serving.moe.experts_hit": hit, "serving.moe.experts_held": held,
         "serving.moe.units_walked": walked, "serving.moe.units_live": live}
    return ({k: 100 for k in c}, {}, {}), \
        ({k: 100 + v for k, v in c.items()}, {}, {})


def facts(with_counters=True):
    """One decode chunk (step 1, traced) of two sequences of 1,000 and
    2,000 tokens; one prefill chunk of 200 real tokens at position 256,
    the last of its prompt; a later chunk outside the trace."""
    reqs = []
    for idx, p in enumerate((1000, 2000)):
        r = Req(idx, np.zeros(p, np.int32), 64)
        r.token_step = [0] + [1] * 16 + [3] * 16
        reqs.append(r)
    r = Req(2, np.zeros(456, np.int32), 8)
    r.rid = 7
    reqs.append(r)
    steps = [(9.0, 9.5, "prefill"), (11.0, 11.5, "decode"),
             (12.0, 12.1, "prefill"), (19.0, 19.5, "decode")]
    journal = [{"ev": "prefill_chunk", "rid": 7, "ts": 12.1, "n": 200,
                "pos": 456, "c": 256},
               {"ev": "prefill_chunk", "rid": 7, "ts": 30.0, "n": 9,
                "pos": 9, "c": 64}]
    s0, s1 = counters(2000, 1000, 990, 1000) if with_counters \
        else (({}, {}, {}), ({}, {}, {}))
    return {"requests": reqs, "steps": steps, "journal": journal,
            "stats0": s0, "stats1": s1, "window_compiles": 0}


def ctx(trace, f):
    return {"config": config(), "trace": trace, "facts": f,
            "peaks": {"bf16_flops_per_s": BF16, "hbm_bytes_per_s": HBM},
            "traced": (10.0, 18.0)}


DECODE = "jit_pt_hybrid_decode_chunk(1234)"
PREFILL = "jit_pt_hybrid_prefill_chunk(99)"


def decode_least():
    gh, cfg = kernels(), config()
    flops = nbytes = 0
    for j in range(16):
        live = [1000 + j, 2000 + j]
        flops += gh.decode_step_flops(cfg, live, 0.5)
        nbytes += gh.decode_step_bytes(cfg, live)
    return flops, nbytes


def test_decode_step_readers():
    flops, nbytes = decode_least()
    assert nbytes / HBM > flops / BF16           # bandwidth-bound
    tr = summary(modules=[(DECODE, 2 * nbytes / HBM, 1),
                          ("jit__unknown(5)", 9.0, 3)])
    f = facts()
    assert reader("decode_step_roofline.rag").read(ctx(tr, f)) \
        == pytest.approx(50.0)
    assert reader("decode_step_mfu.rag").read(ctx(tr, f)) \
        == pytest.approx(100 * (flops / BF16) / (2 * nbytes / HBM))
    assert reader("decode_step_ms.rag").read(ctx(tr, f)) \
        == pytest.approx(1e3 * 2 * nbytes / HBM / 16)
    # the uniform model's decode program is not this one; no counters
    old = summary(modules=[("jit__unknown(5)", 9.0, 3)])
    for name in ("decode_step_roofline.rag", "decode_step_mfu.rag",
                 "decode_step_ms.rag"):
        assert reader(name).read(ctx(old, f)) is None
    assert reader("decode_step_roofline.rag").read(
        ctx(tr, facts(with_counters=False))) is None
    assert reader("decode_step_ms.rag").read(ctx(None, f)) is None


def test_prefill_readers():
    gh, cfg = kernels(), config()
    flops = gh.prefill_chunk_flops(cfg, 256, 200, True, 0.5)
    tr = summary(modules=[(PREFILL, 4 * flops / BF16, 2),
                          ("jit__chunk_prefill_fn(7)", 9.0, 1)])
    assert reader("prefill_step_mfu.rag").read(ctx(tr, facts())) \
        == pytest.approx(25.0)
    assert reader("prefill_chunk_ms.rag").read(ctx(tr, facts())) \
        == pytest.approx(1e3 * 2 * flops / BF16)
    old = summary(modules=[("jit__chunk_prefill_fn(7)", 9.0, 1)])
    assert reader("prefill_step_mfu.rag").read(ctx(old, facts())) is None
    assert reader("prefill_chunk_ms.rag").read(ctx(old, facts())) is None


def test_kernel_rooflines():
    gh, cfg, f = kernels(), config(), facts()
    # 32 tokens decoded in the traced chunk, 16 device steps
    ssm = gh.ssm_decode_bytes(cfg, 32) / HBM
    moe = 16 * gh.moe_stream_bytes(cfg) / HBM
    ssd = max(gh.ssd_chunk_bytes(cfg, 200) / HBM,
              gh.ssd_chunk_flops(cfg, 200) / BF16)
    assert moe > gh.moe_stream_flops(cfg, 32, 0.5) / BF16
    tr = summary(ops=[
        ("pt_ssm_decode_update (f32[9,64,128,8192], f32[64,1,8192])",
         4 * ssm, 144),
        ("pt_moe_stream_experts f32[64,4096]", 2 * moe, 160),
        ("pt_ssd_chunk_scan.3 (f32[128,256,64], f32[128,128,64])",
         10 * ssd, 9),
        ("pt_moe_stream_experts_v2 f32[64,4096]", 9.0, 1)])
    assert reader("ssm_decode_roofline.rag").read(ctx(tr, f)) \
        == pytest.approx(25.0)
    assert reader("moe_experts_roofline.rag").read(ctx(tr, f)) \
        == pytest.approx(50.0)
    assert reader("ssd_prefill_roofline.rag").read(ctx(tr, f)) \
        == pytest.approx(10.0)
    old = summary(ops=[("closed_call.3 f32[64,4096]", 9.0, 1)])
    for name in ("ssm_decode_roofline.rag", "moe_experts_roofline.rag",
                 "ssd_prefill_roofline.rag"):
        assert reader(name).read(ctx(old, f)) is None
        assert reader(name).read(ctx(None, f)) is None


def test_counter_and_span_readers():
    f = facts()
    assert reader("picks_here_share.rag").read(ctx(None, f)) == 50.0
    assert reader("experts_hit_share.rag").read(ctx(None, f)) == 99.0
    none = facts(with_counters=False)
    assert reader("picks_here_share.rag").read(ctx(None, none)) is None
    assert reader("experts_hit_share.rag").read(ctx(None, none)) is None
    assert reader("window_compiles.rag").read(ctx(None, f)) == 0
    # 20 grouped GEMMs of 93 units walked, half of them owning a row
    assert reader("units_live_share.rag").read(ctx(None, f)) == 50.0
    assert reader("units_live_share.rag").read(ctx(None, none)) is None
    f["stats1"] = counters(2000, 1000, 990, 1000, walked=0, live=0)[1]
    assert reader("units_live_share.rag").read(ctx(None, f)) is None
    f["stats0"] = ({}, {}, {"serve.step.total_ms": (10, 100.0),
                            "serve.step.run_ms": (10, 90.0)})
    f["stats1"] = ({}, {}, {"serve.step.total_ms": (30, 400.0),
                            "serve.step.run_ms": (30, 350.0)})
    assert reader("step_host_ms.rag").read(ctx(None, f)) \
        == pytest.approx((300.0 - 260.0) / 20)
    assert reader("step_host_ms.rag").read(ctx(None, none)) is None
    tr = summary()
    tr.window_s, tr.busy_s = 8.0, 7.6
    assert reader("device_idle.rag").read(ctx(tr, f)) == pytest.approx(5.0)
    assert reader("device_idle.rag").read(ctx(None, f)) is None
