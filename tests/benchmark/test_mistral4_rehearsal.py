"""``Mistral-Small-4-119B-2603.longdoc-saturated`` rehearsed on the CPU at
toy widths through the code path a chip run takes (``harness.run_cell``, the
look for a chip skipped); the real cell's files as ``BENCHMARK.json`` names
them; and each ``.longdoc`` reader against a hand-built trace summary and
hand-built facts: the number a hand count gives, and None where its kernel,
program or counter is not there (as on the parent of the PR that added
them)."""
import io
import json
import os

import numpy as np
import pytest

import bench_toy
import mistral4_toy
from benchmark import harness
from benchmark.traffic_gen import Req, closed_loop, quantile_lengths
from benchmark.trace_reduce import TraceSummary

BF16, HBM = 197e12, 819e9
CELL = "Mistral-Small-4-119B-2603.longdoc-saturated"
LONGDOC = ["prefill_chunk_ms", "prefill_step_mfu", "decode_step_ms",
           "decode_step_roofline", "decode_step_mfu", "mla_prefill_roofline",
           "mla_decode_roofline", "latent_attn_busy_share",
           "moe_experts_roofline", "experts_hit_share", "picks_here_share",
           "units_live_share", "kv_walked_share", "step_host_ms",
           "device_idle", "window_compiles"]


# ------------------------------------------------------ the real files

def test_the_cell_as_benchmark_json_names_it():
    cell = harness.Cell(bench_toy.REPO, CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "serve"
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    assert [m["name"] for m in cell.per_layer] \
        == [n + ".longdoc" for n in LONGDOC]
    assert all(m["moves"] == "serve_tok_s" and m["workloads"] == [CELL]
               for m in cell.per_layer)
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]).read)
    cfg = cell.config
    assert cfg["family"] == "mistral4_mla"
    for f in ("models", "reference", "kernels"):
        assert os.path.exists(os.path.join(cell.bench_dir, f,
                                           "mistral4_mla.py"))
    # the published widths, unchanged, and the catalog's other numbers
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["router_width"], cfg["n_shared_experts"]) \
        == (4096, 32, 1024, 256, 64, 64, 128, 2048, 4, 128, 1)
    assert cfg["rope_parameters"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 128,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 8192, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"}
    entry = next(c for c in bench_toy.real_benchmark()["configs"]
                 if c["name"] == "Mistral-Small-4-119B-2603")
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) \
        == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["experts_held"]) == (5, 32, 32768, [0, 32])
    assert cfg["published"] == {"num_hidden_layers": 36,
                                "n_routed_experts": 128,
                                "vocab_size": 131072}
    assert cfg["serving"]["slo"]["prefix_cache"] is False
    assert set(cfg["assumed"]) >= {"router_scoring", "softmax_scale",
                                   "query_temperature", "shared_expert",
                                   "modality", "row_layout", "weights"}


def test_the_mix_is_the_issues_and_the_same_work_for_every_seed():
    cell = harness.Cell(bench_toy.REPO, CELL)
    mix, eng = cell.traffic, cell.config["serving"]["engine"]
    assert mix["arrivals"] == {"kind": "closed", "clients": 24,
                               "requests_per_client": 12}
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 16384,
                                 "sigma": 0.5, "min": 4096, "max": 32768}
    assert mix["output_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.4, "min": 64, "max": 768}
    assert mix["schedule_seed"] == 1 and mix["trace_s"] == 8.0
    lens = quantile_lengths(mix["prompt_len"], 24)
    assert lens.min() >= 4096 and lens.max() <= 32768
    assert 17000 < lens.mean() < 19500
    a = closed_loop(mix, 32768, seed=1)
    b = closed_loop(mix, 32768, seed=2 ** 31 + 5)
    size = lambda cl: sorted((len(r.prompt), r.n_out)      # noqa: E731
                             for seq in cl for r in seq[1:])
    assert size(a) == size(b)
    assert not np.array_equal(a[0][1].prompt, b[0][1].prompt)
    assert max(len(r.prompt) + r.n_out for seq in a for r in seq) \
        <= eng["max_length"] == 32768 + 768
    # the pool holds every slot at its longest: no preemption in a window
    assert eng["num_pages"] >= eng["max_batch"] * eng["max_length"] \
        // eng["page_size"]


def test_the_kernels_arithmetic_at_the_published_widths():
    """The issue's own arithmetic: 28.05 M of attention, 25.17 M an expert,
    859.0 M a layer here, 9.13 GB of weights, a 768 B row where K and V a
    head would be 16,384 B."""
    cell = harness.Cell(bench_toy.REPO, CELL)
    mk = harness.load_module(os.path.join(cell.bench_dir, "kernels",
                                          "mistral4_mla.py"), "mk_test")
    cfg = cell.config
    assert mk.attention_params(cfg) == 28_049_408
    assert mk.expert_params(cfg) == mk.shared_params(cfg) == 25_165_824
    assert mk.router_params(cfg) == 524_288
    layer = mk.attention_params(cfg) + mk.shared_params(cfg) \
        + mk.router_params(cfg) + 32 * mk.expert_params(cfg)
    assert abs(layer - 859.0e6) < 0.1e6
    held = 5 * layer + mk.head_params(cfg)
    # the embedding is looked up, not streamed: 9.13 GB with it
    assert abs(2 * (held + mk.head_params(cfg)) - 9.13e9) < 0.01e9
    assert mk.weight_bytes(cfg) == 2 * held + 2 * 5 * mk.router_params(cfg)
    assert (mk.row_width(cfg), mk.latent_row_bytes(cfg)) == (384, 768)
    assert 2 * 32 * (128 + 128) * 2 == 16384 * 2     # K+V a head, bf16
    assert mk.pair_flops(cfg) == 32 * (128 + 128) * 2
    assert mk.moe_stream_bytes(cfg) == 5 * 32 * 25_165_824 * 2


# -------------------------------------------------------- the rehearsal

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return mistral4_toy.make_root(tmp_path_factory.mktemp("mistral4"))


def run(root, trace, seed=2 ** 31 + 3):
    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell(root, mistral4_toy.CELL, seed, 2.0, trace,
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
    assert {"experts_hit_share.longdoc", "picks_here_share.longdoc",
            "units_live_share.longdoc", "kv_walked_share.longdoc",
            "step_host_ms.longdoc", "window_compiles.longdoc"} <= set(m)
    for name in ("picks_here_share", "experts_hit_share",
                 "units_live_share", "kv_walked_share"):
        assert 0 < m[name + ".longdoc"]["value"] <= 100
    assert m["window_compiles.longdoc"]["value"] == 0
    assert "serve_tok_s" not in m


def test_a_wrong_scale_comes_out_not_correct(root, monkeypatch):
    """The timed path broken underneath: the program's softmax scale
    without the ``m^2``. The comparison that decides ``correct`` sees it."""
    from paddle_tpu.incubate.nn.layer_pattern import LatentAttentionSpec

    monkeypatch.setattr(
        LatentAttentionSpec, "softmax_scale",
        property(lambda self: (self.qk_nope_head_dim
                               + self.qk_rope_head_dim) ** -0.5))
    last, _ = run(root, trace=False)
    c = last["compared"]["served_token_gap"]
    assert last["correct"] is False and c["value"] > c["limit"]


# ----------------------------------------------------------- the readers

def reader(name):
    return harness.load_module(
        os.path.join(bench_toy.REPO, "benchmark", "metrics",
                     name + ".longdoc.py"), "longdoc_reader_" + name)


def config():
    return harness.load_json(os.path.join(
        bench_toy.REPO, "benchmark", "configs",
        "Mistral-Small-4-119B-2603.json"))


def kernels():
    return harness.load_module(os.path.join(
        bench_toy.REPO, "benchmark", "kernels", "mistral4_mla.py"),
        "mk_readers")


def summary(ops=(), modules=(), busy=0.0, window=0.0):
    s = TraceSummary()
    for name, seconds, n in ops:
        s.op_s[name], s.op_n[name] = seconds, n
    for name, seconds, n in modules:
        s.module_s[name], s.module_n[name] = seconds, n
    s.busy_s, s.window_s = busy, window
    return s


def counters(**gained):
    names = {"picks": "serving.moe.picks", "here": "serving.moe.picks_here",
             "hit": "serving.moe.experts_hit",
             "held": "serving.moe.experts_held",
             "walked": "serving.moe.units_walked",
             "live": "serving.moe.units_live",
             "pairs": "serving.mla.prefill_pairs",
             "rows": "serving.mla.rows_read",
             "pages": "serving.kv.pages_walked",
             "region": "serving.kv.pages_region"}
    c = {names[k]: v for k, v in gained.items()}
    return ({k: 100 for k in c}, {}, {}), \
        ({k: 100 + v for k, v in c.items()}, {}, {})


def facts(**gained):
    """One decode chunk (step 1, traced) of two sequences of 10,000 and
    20,000 tokens; one prefill chunk of 800 real tokens at position 2,048,
    the last of its prompt; a later chunk outside the trace."""
    reqs = []
    for idx, p in enumerate((10000, 20000)):
        r = Req(idx, np.zeros(p, np.int32), 64)
        r.token_step = [0] + [1] * 16 + [3] * 16
        reqs.append(r)
    r = Req(2, np.zeros(2848, np.int32), 8)
    r.rid = 7
    reqs.append(r)
    steps = [(9.0, 9.5, "prefill"), (11.0, 11.5, "decode"),
             (12.0, 12.1, "prefill"), (19.0, 19.5, "decode")]
    journal = [{"ev": "prefill_chunk", "rid": 7, "ts": 12.1, "n": 800,
                "pos": 2848, "c": 1024},
               {"ev": "prefill_chunk", "rid": 7, "ts": 30.0, "n": 9,
                "pos": 9, "c": 512}]
    s0, s1 = counters(**gained) if gained else (({}, {}, {}), ({}, {}, {}))
    return {"requests": reqs, "steps": steps, "journal": journal,
            "stats0": s0, "stats1": s1, "window_compiles": 0}


PICKS = dict(picks=4000, here=1000, hit=80, held=160)


def ctx(trace, f):
    return {"config": config(), "trace": trace, "facts": f,
            "peaks": {"bf16_flops_per_s": BF16, "hbm_bytes_per_s": HBM},
            "traced": (10.0, 18.0)}


DECODE = "jit_pt_hybrid_decode_chunk(1234)"
PREFILL = "jit_pt_hybrid_prefill_chunk(99)"


def decode_least():
    mk, cfg = kernels(), config()
    flops = nbytes = 0
    for j in range(16):
        live = [10000 + j, 20000 + j]
        flops += mk.decode_step_flops(cfg, live, 0.25)
        nbytes += mk.decode_step_bytes(cfg, live)
    return flops, nbytes


def test_decode_step_readers():
    flops, nbytes = decode_least()
    assert nbytes / HBM > flops / BF16           # bandwidth-bound
    tr = summary(modules=[(DECODE, 2 * nbytes / HBM, 1),
                          ("jit__unknown(5)", 9.0, 3)])
    f = facts(**PICKS)
    assert reader("decode_step_roofline").read(ctx(tr, f)) \
        == pytest.approx(50.0)
    assert reader("decode_step_mfu").read(ctx(tr, f)) \
        == pytest.approx(100 * (flops / BF16) / (2 * nbytes / HBM))
    assert reader("decode_step_ms").read(ctx(tr, f)) \
        == pytest.approx(1e3 * 2 * nbytes / HBM / 16)
    old = summary(modules=[("jit__unknown(5)", 9.0, 3)])
    for name in ("decode_step_roofline", "decode_step_mfu",
                 "decode_step_ms"):
        assert reader(name).read(ctx(old, f)) is None
    assert reader("decode_step_roofline").read(ctx(tr, facts())) is None
    assert reader("decode_step_ms").read(ctx(None, f)) is None


def test_prefill_readers():
    mk, cfg = kernels(), config()
    flops = mk.prefill_chunk_flops(cfg, 2048, 800, True, 0.25)
    tr = summary(modules=[(PREFILL, 4 * flops / BF16, 2),
                          ("jit__chunk_prefill_fn(7)", 9.0, 1)])
    f = facts(**PICKS)
    assert reader("prefill_step_mfu").read(ctx(tr, f)) \
        == pytest.approx(25.0)
    assert reader("prefill_chunk_ms").read(ctx(tr, f)) \
        == pytest.approx(1e3 * 2 * flops / BF16)
    old = summary(modules=[("jit__chunk_prefill_fn(7)", 9.0, 1)])
    assert reader("prefill_step_mfu").read(ctx(old, f)) is None
    assert reader("prefill_chunk_ms").read(ctx(old, f)) is None
    assert reader("prefill_step_mfu").read(ctx(tr, facts())) is None


def test_latent_attention_kernel_readers():
    mk, cfg = kernels(), config()
    # the traced chunk: 800 rows at 2,048, five layers; the traced decode
    # chunk: 16 steps of 10,000.. and 20,000.. cached rows, five layers
    pairs = 5 * mk.causal_pairs(2048, 800)
    rows = 5 * sum(10000 + j + 20000 + j for j in range(16))
    f = facts(pairs=pairs, rows=rows, **PICKS)
    pre = pairs * 32 * 256 * 2 / BF16
    dec = rows * 768 / HBM
    tr = summary(ops=[
        ("pt_mla_paged_prefill.3 (f32[32768,256], bf16[251840,16,384])",
         5 * pre, 5),
        ("pt_mla_paged_decode (f32[24,32,256], bf16[251840,16,384])",
         2 * dec, 80),
        ("pt_mla_paged_decode_v2 f32[24,32,256]", 9.0, 1)],
        busy=10 * (5 * pre + 2 * dec), window=8.0)
    assert reader("mla_prefill_roofline").read(ctx(tr, f)) \
        == pytest.approx(20.0)
    assert reader("mla_decode_roofline").read(ctx(tr, f)) \
        == pytest.approx(50.0)
    assert reader("latent_attn_busy_share").read(ctx(tr, f)) \
        == pytest.approx(10.0)
    # the parent has neither the kernels nor the counters
    old = summary(ops=[("closed_call.3 f32[64,4096]", 9.0, 1)], busy=9.0,
                  window=9.5)
    for name in ("mla_prefill_roofline", "mla_decode_roofline",
                 "latent_attn_busy_share"):
        assert reader(name).read(ctx(old, f)) is None
        assert reader(name).read(ctx(None, f)) is None
    assert reader("mla_prefill_roofline").read(ctx(tr, facts())) is None
    assert reader("mla_decode_roofline").read(ctx(tr, facts())) is None


def test_expert_stream_roofline():
    mk, cfg, f = kernels(), config(), facts(**PICKS)
    moe = 16 * mk.moe_stream_bytes(cfg) / HBM    # 32 tokens, 16 steps
    assert moe > mk.moe_stream_flops(cfg, 32, 0.25) / BF16
    tr = summary(ops=[("pt_moe_stream_experts f32[32,4096]", 2 * moe, 80)])
    assert reader("moe_experts_roofline").read(ctx(tr, f)) \
        == pytest.approx(50.0)
    assert reader("moe_experts_roofline").read(ctx(tr, facts())) is None
    assert reader("moe_experts_roofline").read(ctx(None, f)) is None


def test_counter_and_span_readers():
    f = facts(walked=1860, live=930, pages=300, region=1000, **PICKS)
    assert reader("picks_here_share").read(ctx(None, f)) == 25.0
    assert reader("experts_hit_share").read(ctx(None, f)) == 50.0
    assert reader("units_live_share").read(ctx(None, f)) == 50.0
    assert reader("kv_walked_share").read(ctx(None, f)) == 30.0
    none = facts()
    for name in ("picks_here_share", "experts_hit_share",
                 "units_live_share", "kv_walked_share"):
        assert reader(name).read(ctx(None, none)) is None
    assert reader("window_compiles").read(ctx(None, f)) == 0
    f["stats0"] = ({}, {}, {"serve.step.total_ms": (10, 100.0),
                            "serve.step.run_ms": (10, 90.0)})
    f["stats1"] = ({}, {}, {"serve.step.total_ms": (30, 400.0),
                            "serve.step.run_ms": (30, 350.0)})
    assert reader("step_host_ms").read(ctx(None, f)) \
        == pytest.approx((300 - 260) / 20)
    assert reader("step_host_ms").read(ctx(None, none)) is None
    tr = summary(busy=7.2, window=8.0)
    assert reader("device_idle").read(ctx(tr, f)) == pytest.approx(10.0)
    assert reader("device_idle").read(ctx(None, f)) is None
