"""Toy widths of ``family: granite_hybrid`` for the CPU tests: the real
configuration file with every size cut (the widths are what the chip runs;
the tests check the mathematics and the control flow)."""
import json
import os

import bench_toy

CELL = "toy-granite.toy-rag"


def config(**over) -> dict:
    with open(os.path.join(bench_toy.REPO, "benchmark", "configs",
                           "granite-4.0-h-small.json")) as f:
        c = json.load(f)
    c.update(
        name="toy-granite", hidden_size=64, vocab_size=128,
        num_hidden_layers=4,
        layer_types=["mamba", "mamba", "attention", "mamba"],
        mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
        mamba_chunk_size=16, num_attention_heads=4, num_key_value_heads=2,
        attention_multiplier=0.0625, router_width=8, num_local_experts=4,
        experts_held=[0, 4], num_experts_per_tok=3, intermediate_size=32,
        shared_intermediate_size=48, n_heads=4, head_dim=16,
        weights_dtype="float32",
        serving={"engine": {"max_batch": 4, "page_size": 4,
                            "max_length": 160, "decode_chunk": 4,
                            "num_pages": 159, "prompt_bucket": 8},
                 "slo": {"prefill_chunk": 32, "prefix_cache": False,
                         "ttft_weight": 4.0},
                 "flags": {"FLAGS_serve_journal_events": 65536}},
        correct={"served_token_gap_limit": 2e-5})
    c.update(over)
    return c


TOY_RAG = {
    "driver": "serve",
    "arrivals": {"kind": "closed", "clients": 4,
                 "requests_per_client": 400},
    "lead_in_s": 0.5,
    "prompt_len": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                   "min": 8, "max": 100},
    "output_len": {"dist": "lognormal", "median": 12, "sigma": 0.35,
                   "min": 4, "max": 24},
    "check_requests": 4, "trace_s": 1.0, "schedule_seed": 1,
}


def make_root(tmp):
    """bench_toy's checkout plus the toy granite configuration, its mix,
    its cell and the real ``.rag`` metrics pointed at that cell — all as
    new files and new list entries."""
    root = bench_toy.make_root(tmp)
    bench_toy.dump(root, "benchmark/configs/toy-granite.json", config())
    bench_toy.dump(root, "benchmark/traffic/toy-rag.json", TOY_RAG)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy-granite", "source": "toy",
                             "file": "benchmark/configs/toy-granite.json",
                             "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": CELL, "config": "toy-granite",
                               "traffic": "toy-rag", "chips": 1,
                               "why": "toy"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tok_s":
            m["workloads"].append(CELL)
    for m in bench["per_layer"]:
        if m["name"].endswith(".rag"):
            m["workloads"] = [CELL]
    bench_toy.dump(root, "BENCHMARK.json", bench)
    return root
