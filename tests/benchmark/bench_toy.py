"""A toy checkout for the benchmark's tests: the real ``benchmark/``
directory copied whole, plus a toy configuration, toy traffic mixes and a
``BENCHMARK.json`` that names them. Nothing of the real files is edited,
which is what a later PR that adds a cell does."""
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TOY_SERVING = {
    "engine": {"max_batch": 4, "page_size": 16, "max_length": 256,
               "decode_chunk": 4, "num_pages": 63, "prompt_bucket": 16},
    "slo": {"prefill_chunk": 32},
    "flags": {"FLAGS_serve_journal_events": 65536},
}
TOY_OPEN = {
    "driver": "serve", "arrivals": {"kind": "poisson", "rate_rps": 6.0},
    "lead_in_s": 0.5,
    "prompt_len": {"dist": "lognormal", "median": 48, "sigma": 0.6,
                   "min": 8, "max": 128},
    "output_len": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                   "min": 4, "max": 32},
    "check_requests": 4, "trace_s": 1.0,
}
TOY_CLOSED = dict(
    TOY_OPEN, arrivals={"kind": "closed", "clients": 4,
                        "requests_per_client": 400},
    prompt_len={"dist": "uniform", "min": 16, "max": 48})


TOY_TRAIN = {"driver": "train", "training": {"batch": 4, "seq": 32},
             "trace_s": 1.0}


def real_benchmark() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def make_root(tmp, extra_cells=(), extra_metrics=(), vocab=512):
    """A checkout under ``tmp`` whose cells are toy copies of the real ones:
    same drivers, same metric names, toy sizes."""
    root = os.path.join(str(tmp), "checkout")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    real = real_benchmark()
    with open(os.path.join(REPO, "benchmark", "configs",
                           "gpt3-1.3b.json")) as f:
        cfg = json.load(f)
    cfg.update(name="toy-gpt", d_model=128, n_layers=2, n_heads=4,
               head_dim=32, d_ff=512, vocab_size=vocab, serving=TOY_SERVING,
               correct={"served_token_gap_limit": 0.05,
                        "training": {"loss_gap.1": 0.01, "loss_gap.2": 0.01,
                                     "loss_gap.3": 0.01,
                                     "grad_norm_gap": 0.1,
                                     "grad_vector_gap": 0.1,
                                     "change_norm_gap": 0.1}})
    dump(root, "benchmark/configs/toy-gpt.json", cfg)
    dump(root, "benchmark/traffic/toy-open.json", TOY_OPEN)
    dump(root, "benchmark/traffic/toy-closed.json", TOY_CLOSED)
    dump(root, "benchmark/traffic/toy-train.json", TOY_TRAIN)
    rename = {"gpt3-1.3b.chat-knee80": "toy-gpt.toy-open",
              "gpt3-1.3b.reason-saturated": "toy-gpt.toy-closed",
              "gpt3-1.3b.pretrain-s2048": "toy-gpt.toy-train"}
    cells = [{"name": new, "config": "toy-gpt",
              "traffic": new.split(".", 1)[1], "chips": 1, "why": "toy"}
             for new in rename.values()] + list(extra_cells)

    def moved(metric):
        m = dict(metric)
        if "workloads" in m:
            m["workloads"] = [rename[w] for w in m["workloads"]
                              if w in rename]
        return m

    bench = dict(real, run_seconds=2,
                 configs=[{"name": "toy-gpt", "source": "toy",
                           "file": "benchmark/configs/toy-gpt.json",
                           "reduced": [], "why": "toy"}],
                 workloads=cells,
                 end_to_end=[moved(m) for m in real["end_to_end"]],
                 per_layer=[moved(m) for m in real["per_layer"]]
                 + list(extra_metrics))
    dump(root, "BENCHMARK.json", bench)
    return root


def dump(root, rel, obj):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)
