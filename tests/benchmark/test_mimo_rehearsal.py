"""``MiMo-V2-Flash.mixedlen-saturated`` rehearsed on the CPU at toy widths
through the code path a chip run takes (``harness.run_cell``, the look for a
chip skipped); the real cell's files as ``BENCHMARK.json`` names them; and
each ``.mixed`` reader against a hand-built trace summary and hand-built
facts: the number a hand count gives, and None where its kernel, program or
counter is not there (as on a tree that has none of them)."""
import io
import json
import os

import numpy as np
import pytest

import bench_toy
import mimo_toy
from benchmark import harness
from benchmark.traffic_gen import Req, closed_loop, quantile_lengths
from benchmark.trace_reduce import TraceSummary

BF16, HBM = 197e12, 819e9
CELL = "MiMo-V2-Flash.mixedlen-saturated"
MIXED = ["prefill_chunk_ms", "prefill_step_mfu", "decode_step_ms",
         "decode_step_roofline", "decode_step_mfu",
         "full_attn_prefill_roofline", "window_attn_prefill_roofline",
         "full_attn_decode_roofline", "window_attn_decode_roofline",
         "attn_busy_share", "moe_experts_roofline", "experts_hit_share",
         "picks_here_share", "units_live_share", "kv_walked_share",
         "step_host_ms", "device_idle", "window_compiles"]


# ------------------------------------------------------ the real files

def test_the_cell_as_benchmark_json_names_it():
    cell = harness.Cell(bench_toy.REPO, CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "serve"
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    assert [m["name"] for m in cell.per_layer] \
        == [n + ".mixed" for n in MIXED]
    assert all(m["moves"] == "serve_tok_s" and m["workloads"] == [CELL]
               for m in cell.per_layer)
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]).read)
    cfg = cell.config
    assert cfg["family"] == "mimo_v2_flash"
    for f in ("models", "reference", "kernels"):
        assert os.path.exists(os.path.join(cell.bench_dir, f,
                                           "mimo_v2_flash.py"))
    # every number of the catalog's entry, unchanged but for `reduced`
    entry = next(c for c in bench_toy.real_benchmark()["configs"]
                 if c["name"] == "MiMo-V2-Flash")
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) \
        == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["swa_num_key_value_heads"],
            cfg["head_dim"], cfg["v_head_dim"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["router_width"], cfg["sliding_window"],
            cfg["partial_rotary_factor"], cfg["attention_value_scale"]) \
        == (4096, 64, 4, 8, 192, 128, 16384, 2048, 8, 256, 128, 0.334,
            0.707)
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["experts_held"]) == (7, 16, 19072, [0, 16])
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 256,
                                "vocab_size": 152576}
    assert len(cfg["hybrid_layer_pattern"]) == 48 \
        and len(cfg["moe_layer_freq"]) == 48
    assert cfg["serving"]["slo"]["prefix_cache"] is False
    assert set(cfg["assumed"]) >= {"sink", "window", "rope", "value_scale",
                                   "router_scoring", "norm", "modality",
                                   "weights", "row_layout"}


def test_the_mix_is_the_cells_and_the_same_work_for_every_seed():
    cell = harness.Cell(bench_toy.REPO, CELL)
    mix, eng = cell.traffic, cell.config["serving"]["engine"]
    assert mix["arrivals"] == {"kind": "closed", "clients": 32,
                               "requests_per_client": 12}
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 8192,
                                 "sigma": 0.9, "min": 1024, "max": 32768}
    assert mix["output_len"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 0.5, "min": 256, "max": 2048}
    assert mix["schedule_seed"] == 1 and mix["trace_s"] == 8.0
    assert mix["check_requests"] == 5
    lens = quantile_lengths(mix["prompt_len"], 32)
    assert lens.min() >= 1024 and lens.max() <= 32768
    a = closed_loop(mix, 19072, seed=1)
    b = closed_loop(mix, 19072, seed=2 ** 31 + 5)
    size = lambda cl: sorted((len(r.prompt), r.n_out)      # noqa: E731
                             for seq in cl for r in seq[1:])
    assert size(a) == size(b)
    assert not np.array_equal(a[0][1].prompt, b[0][1].prompt)
    assert max(len(r.prompt) + r.n_out for seq in a for r in seq) \
        <= eng["max_length"] == 32768 + 2048
    # the pool holds every slot at its longest: no preemption in a window
    assert eng["num_pages"] >= eng["max_batch"] * eng["max_length"] \
        // eng["page_size"]


def test_the_kernels_arithmetic_at_the_published_widths():
    """The configuration's own arithmetic: 89.13 M of full attention,
    94.37 M of window attention, 25.17 M an expert, 201.33 M of dense MLP,
    3.43 B parameters = 6.86 GB on the chip, a 2,560 B packed row, 5,120 B
    of K+V a window token."""
    cell = harness.Cell(bench_toy.REPO, CELL)
    mk = harness.load_module(os.path.join(cell.bench_dir, "kernels",
                                          "mimo_v2_flash.py"), "mimo_mk")
    cfg = cell.config
    assert mk.attention_params(cfg, False) == 89_128_960
    assert mk.attention_params(cfg, True) == 94_371_840
    assert mk.expert_params(cfg) == 25_165_824
    assert mk.dense_mlp_params(cfg) == 201_326_592
    assert (mk.n_full(cfg), mk.n_window(cfg), mk.n_moe(cfg)) == (2, 5, 6)
    assert abs(2 * mk.weight_params(cfg) - 6.86e9) < 0.02 * 6.86e9
    assert (mk.row_bytes(cfg, False), mk.row_bytes(cfg, True)) \
        == (2560, 5120)
    assert mk.pair_flops(cfg) == 64 * 320 * 2
    assert mk.moe_stream_bytes(cfg) == 6 * 16 * 25_165_824 * 2
    # the full layers' pool at every slot's longest, and the ring
    eng = cfg["serving"]["engine"]
    pool = eng["num_pages"] * eng["page_size"] * 2 * 2560
    assert abs(pool - 5.70e9) < 0.01e9
    assert 5 * 32 * 8 * 128 * (256 + 128) * 2 < 0.13e9


# -------------------------------------------------------- the rehearsal

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return mimo_toy.make_root(tmp_path_factory.mktemp("mimo"))


def run(root, trace, seed=2 ** 31 + 3):
    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell(root, mimo_toy.CELL, seed, 2.0, trace,
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
    assert {"experts_hit_share.mixed", "picks_here_share.mixed",
            "units_live_share.mixed", "kv_walked_share.mixed",
            "step_host_ms.mixed", "window_compiles.mixed"} <= set(m)
    for name in ("picks_here_share", "experts_hit_share",
                 "units_live_share", "kv_walked_share"):
        assert 0 < m[name + ".mixed"]["value"] <= 100
    assert m["window_compiles.mixed"]["value"] == 0
    assert "serve_tok_s" not in m


def test_a_dropped_sink_comes_out_not_correct(root, monkeypatch):
    """The timed path broken underneath: the window layers' softmax without
    its sink. The comparison that decides ``correct`` sees it."""
    from paddle_tpu.nn.functional import mixed_attention

    real = mixed_attention.swa_decode_attend

    def no_sink(q, k, v, ring, layer, lens, active, sink=None, **kw):
        return real(q, k, v, ring, layer, lens, active, None, **kw)

    monkeypatch.setattr(mixed_attention, "swa_decode_attend", no_sink)
    last, _ = run(root, trace=False)
    c = last["compared"]["served_token_gap"]
    assert last["correct"] is False and c["value"] > c["limit"]


# ----------------------------------------------------------- the readers

def reader(name):
    return harness.load_module(
        os.path.join(bench_toy.REPO, "benchmark", "metrics",
                     name + ".mixed.py"), "mixed_reader_" + name)


def config():
    return harness.load_json(os.path.join(
        bench_toy.REPO, "benchmark", "configs", "MiMo-V2-Flash.json"))


def kernels():
    return harness.load_module(os.path.join(
        bench_toy.REPO, "benchmark", "kernels", "mimo_v2_flash.py"),
        "mimo_mk_readers")


def summary(ops=(), modules=(), busy=0.0, window=0.0):
    s = TraceSummary()
    for name, seconds, n in ops:
        s.op_s[name], s.op_n[name] = seconds, n
    for name, seconds, n in modules:
        s.module_s[name], s.module_n[name] = seconds, n
    s.busy_s, s.window_s = busy, window
    return s


NAMES = {"picks": "serving.moe.picks", "here": "serving.moe.picks_here",
         "hit": "serving.moe.experts_hit", "held": "serving.moe.experts_held",
         "walked": "serving.moe.units_walked",
         "live": "serving.moe.units_live",
         "full_pairs": "serving.attn.prefill_pairs",
         "win_pairs": "serving.window.prefill_pairs",
         "full_rows": "serving.attn.rows_read",
         "win_rows": "serving.window.rows_read",
         "pages": "serving.kv.pages_walked",
         "region": "serving.kv.pages_region"}


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
    c = {NAMES[k]: v for k, v in gained.items()}
    s0 = ({k: 100 for k in c}, {}, {})
    s1 = ({k: 100 + v for k, v in c.items()}, {}, {})
    return {"requests": reqs, "steps": steps, "journal": journal,
            "stats0": s0, "stats1": s1, "window_compiles": 0}


PICKS = dict(picks=4000, here=250, hit=80, held=160)


def ctx(trace, f):
    return {"config": config(), "trace": trace, "facts": f,
            "peaks": {"bf16_flops_per_s": BF16, "hbm_bytes_per_s": HBM},
            "traced": (10.0, 18.0)}


DECODE = "jit_pt_hybrid_decode_chunk(1234)"
PREFILL = "jit_pt_hybrid_prefill_chunk(99)"


def test_decode_step_readers():
    mk, cfg = kernels(), config()
    flops = nbytes = 0
    for j in range(16):
        live = [10000 + j, 20000 + j]
        flops += mk.decode_step_flops(cfg, live, 0.0625)
        nbytes += mk.decode_step_bytes(cfg, live)
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


def test_prefill_readers():
    mk, cfg = kernels(), config()
    flops = mk.prefill_chunk_flops(cfg, 2048, 800, True, 0.0625)
    # 800 rows past 2,048: every window row sees 128 positions
    assert mk.window_pairs(2048, 800, 128) == 800 * 128
    assert mk.window_pairs(0, 3, 128) == 6
    tr = summary(modules=[(PREFILL, 4 * flops / BF16, 2)])
    f = facts(**PICKS)
    assert reader("prefill_step_mfu").read(ctx(tr, f)) \
        == pytest.approx(25.0)
    assert reader("prefill_chunk_ms").read(ctx(tr, f)) \
        == pytest.approx(1e3 * 2 * flops / BF16)
    old = summary(modules=[("jit__chunk_prefill_fn(7)", 9.0, 1)])
    assert reader("prefill_step_mfu").read(ctx(old, f)) is None
    assert reader("prefill_chunk_ms").read(ctx(old, f)) is None


def test_attention_kernel_readers():
    mk, cfg = kernels(), config()
    full_pairs = 2 * mk.causal_pairs(2048, 800)
    win_pairs = 5 * mk.window_pairs(2048, 800, 128)
    full_rows = 2 * sum(10000 + j + 20000 + j for j in range(16))
    win_rows = 5 * 32 * 128 * 16
    f = facts(full_pairs=full_pairs, win_pairs=win_pairs,
              full_rows=full_rows, win_rows=win_rows, **PICKS)
    pf = full_pairs * 64 * 320 * 2 / BF16
    pw = win_pairs * 64 * 320 * 2 / BF16
    df = full_rows * 2560 / HBM
    dw = win_rows * 5120 / HBM
    tr = summary(ops=[
        ("pt_gqa_paged_prefill.3 (f32[1024,8192])", 5 * pf, 2),
        ("pt_swa_prefill f32[1024,8192]", 4 * pw, 5),
        ("pt_gqa_paged_decode (f32[32,64,512])", 2 * df, 32),
        ("pt_swa_decode.1 f32[32,8,8,128]", 2 * dw, 80),
        ("pt_swa_decode_v2 f32[1]", 9.0, 1)],
        busy=10 * (5 * pf + 4 * pw + 2 * df + 2 * dw), window=8.0)
    assert reader("full_attn_prefill_roofline").read(ctx(tr, f)) \
        == pytest.approx(20.0)
    assert reader("window_attn_prefill_roofline").read(ctx(tr, f)) \
        == pytest.approx(25.0)
    assert reader("full_attn_decode_roofline").read(ctx(tr, f)) \
        == pytest.approx(50.0)
    assert reader("window_attn_decode_roofline").read(ctx(tr, f)) \
        == pytest.approx(50.0)
    assert reader("attn_busy_share").read(ctx(tr, f)) \
        == pytest.approx(10.0)
    # the parent has neither the kernels nor the counters
    old = summary(ops=[("closed_call.3 f32[64,4096]", 9.0, 1)], busy=9.0,
                  window=9.5)
    for name in ("full_attn_prefill_roofline", "window_attn_prefill_roofline",
                 "full_attn_decode_roofline", "window_attn_decode_roofline",
                 "attn_busy_share"):
        assert reader(name).read(ctx(old, f)) is None
        assert reader(name).read(ctx(None, f)) is None
        assert reader(name).read(ctx(tr, facts())) is None \
            or name == "attn_busy_share"


def test_expert_stream_roofline():
    mk, cfg, f = kernels(), config(), facts(**PICKS)
    moe = 16 * mk.moe_stream_bytes(cfg) / HBM    # 32 tokens, 16 steps
    assert moe > mk.moe_stream_flops(cfg, 32, 0.0625) / BF16
    tr = summary(ops=[("pt_moe_stream_experts f32[32,4096]", 2 * moe, 96)])
    assert reader("moe_experts_roofline").read(ctx(tr, f)) \
        == pytest.approx(50.0)
    assert reader("moe_experts_roofline").read(ctx(tr, facts())) is None
    assert reader("moe_experts_roofline").read(ctx(None, f)) is None


def test_counter_and_span_readers():
    f = facts(walked=1860, live=930, pages=300, region=1000, **PICKS)
    assert reader("picks_here_share").read(ctx(None, f)) == 6.25
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
