"""Toy widths of ``family: mimo_v2_flash`` for the CPU tests: the real
configuration file with every size cut (the widths are what the chip runs;
the tests check the mathematics and the control flow). The toy window is 8
rows and a prefill chunk 32, so prompts of 40-120 tokens cross the window's
edge inside and between chunks; q / k heads of 24 rotate their first
int(24 x 0.334) = 8 dims."""
import json
import os

import bench_toy

CELL = "toy-mimo.toy-mixedlen"


def config(**over) -> dict:
    with open(os.path.join(bench_toy.REPO, "benchmark", "configs",
                           "MiMo-V2-Flash.json")) as f:
        c = json.load(f)
    c.update(
        name="toy-mimo", hidden_size=64, vocab_size=128,
        num_attention_heads=4, num_key_value_heads=2,
        swa_num_attention_heads=4, swa_num_key_value_heads=2, head_dim=24,
        swa_head_dim=24, v_head_dim=16, swa_v_head_dim=16,
        intermediate_size=48, moe_intermediate_size=16, router_width=8,
        n_routed_experts=4, experts_held=[0, 4], num_experts_per_tok=2,
        n_heads=4, sliding_window=8, sliding_window_size=8,
        weights_dtype="float32",
        serving={"engine": {"max_batch": 4, "page_size": 4,
                            "max_length": 160, "decode_chunk": 4,
                            "num_pages": 159, "prompt_bucket": 8},
                 "slo": {"prefill_chunk": 32, "prefix_cache": False,
                         "ttft_weight": 4.0},
                 "flags": {"FLAGS_serve_journal_events": 65536}},
        correct={"served_token_gap_limit": 1e-4})
    c.update(over)
    return c


TOY_MIXED = {
    "driver": "serve",
    "arrivals": {"kind": "closed", "clients": 4,
                 "requests_per_client": 400},
    "lead_in_s": 0.5,
    "prompt_len": {"dist": "lognormal", "median": 50, "sigma": 0.6,
                   "min": 12, "max": 120},
    "output_len": {"dist": "lognormal", "median": 10, "sigma": 0.4,
                   "min": 4, "max": 24},
    "check_requests": 5, "trace_s": 1.0, "schedule_seed": 1,
}


def make_root(tmp):
    """bench_toy's checkout plus the toy configuration, its mix, its cell
    and the real ``.mixed`` metrics pointed at that cell — all as new
    files and new list entries."""
    root = bench_toy.make_root(tmp)
    bench_toy.dump(root, "benchmark/configs/toy-mimo.json", config())
    bench_toy.dump(root, "benchmark/traffic/toy-mixedlen.json", TOY_MIXED)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy-mimo", "source": "toy",
                             "file": "benchmark/configs/toy-mimo.json",
                             "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": CELL, "config": "toy-mimo",
                               "traffic": "toy-mixedlen", "chips": 1,
                               "why": "toy"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tok_s":
            m["workloads"].append(CELL)
    for m in bench["per_layer"]:
        if m["name"].endswith(".mixed"):
            m["workloads"] = [CELL]
    bench_toy.dump(root, "BENCHMARK.json", bench)
    return root
