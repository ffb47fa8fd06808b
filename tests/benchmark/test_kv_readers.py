"""The three ``kv_walked_share.*`` readers: a value from two snapshots of
the program's counters (``stats0`` / ``stats1`` of the window), and None
against a program that has no such counters (the parent of the PR that
added them walked the whole region and counted nothing)."""
import os

import pytest

import bench_toy
from benchmark import harness

METRICS = ["kv_walked_share.sat", "kv_walked_share.chat",
           "kv_walked_share.rag"]


def reader(name):
    return harness.load_module(
        os.path.join(bench_toy.REPO, "benchmark", "metrics", name + ".py"),
        "kv_reader_" + name.replace(".", "_"))


def facts(walked, region):
    c = {"serving.kv.pages_walked": walked,
         "serving.kv.pages_region": region}
    c = {k: v for k, v in c.items() if v is not None}
    return {"stats0": ({k: 1000 for k in c}, {}, {}),
            "stats1": ({k: 1000 + v for k, v in c.items()}, {}, {})}


@pytest.mark.parametrize("name", METRICS)
def test_share_from_two_snapshots(name):
    # 24 layers x 16 steps of a 1,664-page region, 1,220 pages named
    got = reader(name).read({"facts": facts(24 * 16 * 1220, 24 * 16 * 1664)})
    assert got == pytest.approx(100.0 * 1220 / 1664)


@pytest.mark.parametrize("name", METRICS)
def test_none_without_the_counters(name):
    assert reader(name).read({"facts": facts(None, None)}) is None
    assert reader(name).read({"facts": {}}) is None
    # no decode step in the window: nothing to take a share of
    assert reader(name).read({"facts": facts(0, 0)}) is None


WANT = {"kv_walked_share.sat": ("gpt3-1.3b.reason-saturated", "serve_tok_s"),
        "kv_walked_share.chat": ("gpt3-1.3b.chat-knee80", "tpot_p95_ms"),
        "kv_walked_share.rag": ("granite-4.0-h-small.rag-saturated",
                                "serve_tok_s")}


@pytest.mark.parametrize("name", METRICS)
def test_benchmark_json_lists_each_with_its_one_cell(name):
    """All three are listed, each with the one cell that has its counters
    (``.rag`` since the benchmark PR that could append to the rag cell's
    pinned list in ``test_granite_rehearsal.py``)."""
    bench = harness.load_json(os.path.join(bench_toy.REPO, "BENCHMARK.json"))
    m = {m["name"]: m for m in bench["per_layer"]}[name]
    cell, moves = WANT[name]
    assert (m["workloads"], m["moves"], m["layer"], m["unit"],
            m["better"], m["source"]) == (
                [cell], moves, "kernels", "%", "lower", "program_counter")
