"""Each driver end to end on the CPU at toy widths, through the code path a
chip run takes (``harness.run_cell``), with the look for a chip skipped;
that a cell and a metric added as NEW files are found by name; that the
command itself fails off-chip without printing a result; and that
``correct`` comes out false when the timed path is broken underneath."""
import io
import json
import os
import subprocess
import sys

import pytest

import bench_toy
from benchmark import harness

LAST_LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(root, cell, trace, seed=2 ** 31 + 3, seconds=2.0):
    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell(root, cell, seed, seconds, trace, need_chip=False,
                           out=out, err=err)
    last = json.loads(out.getvalue().strip().splitlines()[-1])
    assert last == json.loads(json.dumps(res))
    assert LAST_LINE_KEYS <= set(last)
    assert list(last)[-1] == "compared"
    assert err.getvalue().strip().splitlines()[-1].startswith("compared ")
    return last


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_toy.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", ["toy-gpt.toy-open", "toy-gpt.toy-closed",
                                  "toy-gpt.toy-train"])
def test_end_to_end_line(root, cell):
    last = run(root, cell, trace=False)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    want = {m["name"] for m in harness.Cell(root, cell).end_to_end}
    assert set(last["metrics"]) == want and "setup_s" in want
    for name, m in last["metrics"].items():
        assert m["value"] > 0 and m["unit"]
    assert last["device"]["platform"] == "cpu"     # named, never a chip's


@pytest.mark.parametrize("cell", ["toy-gpt.toy-open", "toy-gpt.toy-closed",
                                  "toy-gpt.toy-train"])
def test_traced_line_has_what_needs_no_device(root, cell, monkeypatch):
    """Off-chip the trace holds no device plane, so the readers of the
    device trace find nothing and are left out; every other per-layer
    metric of the cell has to be there."""
    monkeypatch.setattr(harness.Tracer, "summary", lambda self, n: None)
    last = run(root, cell, trace=True)
    cellobj = harness.Cell(root, cell)
    want = {m["name"] for m in cellobj.per_layer
            if m["source"] != "device_trace"}
    assert want and want <= set(last["metrics"])
    assert not {m["name"] for m in cellobj.end_to_end} & set(last["metrics"])


def test_new_cell_and_metric_are_found_by_name(tmp_path, monkeypatch):
    """What a later PR does: new files and one new entry each, no file that
    is there edited."""
    cell = {"name": "toy-gpt.toy-burst", "config": "toy-gpt",
            "traffic": "toy-burst", "chips": 1, "why": "toy"}
    metric = {"name": "requests_sent.burst", "unit": "count",
              "better": "higher", "source": "program_counter",
              "layer": "entry", "moves": "ttft_p95_ms",
              "workloads": ["toy-gpt.toy-burst"]}
    root = bench_toy.make_root(tmp_path, extra_cells=[cell],
                               extra_metrics=[metric])
    before = {p: os.path.getmtime(os.path.join(b, p))
              for b, _d, fs in os.walk(root) for p in fs}
    bench_toy.dump(root, "benchmark/traffic/toy-burst.json", dict(
        bench_toy.TOY_OPEN, arrivals={"kind": "gamma", "cv": 3.0,
                                      "rate_rps": 6.0}))
    with open(os.path.join(root, "benchmark", "metrics",
                           "requests_sent.burst.py"), "w") as f:
        f.write("def read(ctx):\n    return len(ctx['facts']['requests'])\n")
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p95_ms", "tpot_p95_ms"):
            m["workloads"].append(cell["name"])
    bench_toy.dump(root, "BENCHMARK.json", bench)
    monkeypatch.setattr(harness.Tracer, "summary", lambda self, n: None)
    last = run(root, cell["name"], trace=True)
    assert last["metrics"]["requests_sent.burst"]["value"] > 0
    after = {p: os.path.getmtime(os.path.join(b, p))
             for b, _d, fs in os.walk(root) for p in fs}
    assert all(after[p] == t for p, t in before.items()
               if p != "BENCHMARK.json")


TRAFFIC = os.path.join(bench_toy.REPO, "benchmark", "traffic")
MIXES = sorted(f[:-len(".json")] for f in os.listdir(TRAFFIC)
               if f.endswith(".json"))
#: a p95 wants ten samples beyond it (choosing-metrics, section 1), which
#: is 200 requests; four fifths of a knee of 4.0 requests/s in the 50 s that
#: ``run_seconds`` allows gives 160 and eight beyond (PERF.md section 4).
#: The floor keeps a mix from falling back to a tail that is ONE request.
MIN_DUE_FOR_A_P95 = 150


@pytest.mark.parametrize("mix", MIXES)
def test_a_mix_has_its_cell_and_a_tail_its_samples(mix):
    """The real files: every mix is some cell's traffic, and an open loop
    whose cell is judged by a 95th percentile offers enough requests in
    one window of ``run_seconds`` that the percentile is not one
    request's draw (chat-steady held 24: PERF.md section 6, PR 33)."""
    bench = bench_toy.real_benchmark()
    cells = [w["name"] for w in bench["workloads"] if w["traffic"] == mix]
    assert len(cells) == 1
    cell = harness.Cell(bench_toy.REPO, cells[0])
    arrivals = cell.traffic.get("arrivals", {})
    tails = [m["name"] for m in cell.end_to_end if "_p95_" in m["name"]]
    if "rate_rps" not in arrivals:
        assert not tails          # a closed loop or a trainer: a rate judges
        return
    assert tails
    due = round(float(arrivals["rate_rps"]) * bench["run_seconds"])
    assert due >= MIN_DUE_FOR_A_P95, (mix, due)


def test_command_fails_off_chip_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(bench_toy.REPO, "benchmark", "run.py"),
         "--workload", "gpt3-1.3b.chat-knee80", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "nothing measured" in p.stderr


def test_altered_tokens_come_out_not_correct(root, monkeypatch):
    """The timed path broken underneath: the decode program's greedy pick
    returns the neighbour of the best token."""
    import jax.numpy as jnp

    from paddle_tpu.inference.engine import GenerationEngine

    good = GenerationEngine._argmax

    def off_by_one(logits):
        return (good(logits) + 1) % jnp.int32(logits.shape[-1])

    monkeypatch.setattr(GenerationEngine, "_argmax",
                        staticmethod(off_by_one))
    last = run(root, "toy-gpt.toy-closed", trace=False)
    assert last["correct"] is False
    c = last["compared"]["served_token_gap"]
    assert c["value"] > c["limit"]
