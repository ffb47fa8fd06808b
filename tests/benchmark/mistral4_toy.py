"""Toy widths of ``family: mistral4_mla`` for the CPU tests: the real
configuration file with every size cut (the widths are what the chip runs;
the tests check the mathematics and the control flow). The toy
``original_max_position_embeddings`` is 16, so the tests' positions lie past
it: the YaRN blend and the query temperature act."""
import json
import os

import bench_toy

CELL = "toy-mistral4.toy-longdoc"


def config(**over) -> dict:
    with open(os.path.join(bench_toy.REPO, "benchmark", "configs",
                           "Mistral-Small-4-119B-2603.json")) as f:
        c = json.load(f)
    rp = dict(c["rope_parameters"], factor=8,
              original_max_position_embeddings=16, beta_fast=4,
              llama_4_scaling_beta=0.3)
    c.update(
        name="toy-mistral4", hidden_size=64, vocab_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=8,
        qk_rope_head_dim=8, qk_head_dim=16, v_head_dim=16, head_dim=16,
        moe_intermediate_size=32, router_width=8, n_routed_experts=4,
        experts_held=[0, 4], num_experts_per_tok=2, n_heads=4,
        rope_parameters=rp, weights_dtype="float32",
        serving={"engine": {"max_batch": 4, "page_size": 4,
                            "max_length": 160, "decode_chunk": 4,
                            "num_pages": 159, "prompt_bucket": 8},
                 "slo": {"prefill_chunk": 32, "prefix_cache": False,
                         "ttft_weight": 4.0},
                 "flags": {"FLAGS_serve_journal_events": 65536}},
        correct={"served_token_gap_limit": 1e-4})
    c.update(over)
    return c


TOY_LONGDOC = {
    "driver": "serve",
    "arrivals": {"kind": "closed", "clients": 4,
                 "requests_per_client": 400},
    "lead_in_s": 0.5,
    "prompt_len": {"dist": "lognormal", "median": 60, "sigma": 0.5,
                   "min": 40, "max": 120},
    "output_len": {"dist": "lognormal", "median": 10, "sigma": 0.4,
                   "min": 4, "max": 24},
    "check_requests": 5, "trace_s": 1.0, "schedule_seed": 1,
}


def make_root(tmp):
    """bench_toy's checkout plus the toy configuration, its mix, its cell
    and the real ``.longdoc`` metrics pointed at that cell — all as new
    files and new list entries."""
    root = bench_toy.make_root(tmp)
    bench_toy.dump(root, "benchmark/configs/toy-mistral4.json", config())
    bench_toy.dump(root, "benchmark/traffic/toy-longdoc.json", TOY_LONGDOC)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy-mistral4", "source": "toy",
                             "file": "benchmark/configs/toy-mistral4.json",
                             "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": CELL, "config": "toy-mistral4",
                               "traffic": "toy-longdoc", "chips": 1,
                               "why": "toy"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tok_s":
            m["workloads"].append(CELL)
    for m in bench["per_layer"]:
        if m["name"].endswith(".longdoc"):
            m["workloads"] = [CELL]
    bench_toy.dump(root, "BENCHMARK.json", bench)
    return root
