"""The per-layer readers that read the program's own names: each against a
hand-built trace summary and hand-built facts gives the number a hand count
gives, and None where its kernel, span or histogram is not there (a program
without them, as the parent of the PR that added them); and a toy checkout
whose BENCHMARK.json lists the two ``step_host_ms`` metrics prints both
from a CPU rehearsal run."""
import io
import json
import os

import numpy as np
import pytest

import bench_toy
from benchmark import harness
from benchmark.traffic_gen import Req
from benchmark.trace_reduce import TraceSummary

BF16, HBM = 197e12, 819e9
D, LAYERS, DFF = 2048, 24, 8192


def reader(name):
    return harness.load_module(
        os.path.join(bench_toy.REPO, "benchmark", "metrics", name + ".py"),
        "span_reader_" + name.replace(".", "_"))


def config():
    return harness.load_json(os.path.join(
        bench_toy.REPO, "benchmark", "configs", "gpt3-1.3b.json"))


def summary(ops=(), modules=()):
    s = TraceSummary()
    for name, seconds, n in ops:
        s.op_s[name], s.op_n[name] = seconds, n
    for name, seconds, n in modules:
        s.module_s[name], s.module_n[name] = seconds, n
    return s


def ctx(trace, facts):
    return {"config": config(), "trace": trace, "facts": facts,
            "peaks": {"bf16_flops_per_s": BF16, "hbm_bytes_per_s": HBM},
            "traced": (10.0, 18.0)}


def decode_facts():
    """One decode chunk (step 1, inside the traced part) that gave 16
    tokens each to two sequences of 100 and 200 tokens; a second chunk
    (step 2) after the trace had stopped."""
    reqs = []
    for idx, p in enumerate((100, 200)):
        r = Req(idx, np.zeros(p, np.int32), 64)
        r.token_step = [0] + [1] * 16 + [2] * 16
        reqs.append(r)
    steps = [(9.0, 9.5, "prefill"), (11.0, 11.2, "decode"),
             (19.0, 19.2, "decode")]
    return {"requests": reqs, "steps": steps, "journal": []}


# rows attended by the chunk's 2 x 16 tokens: sum over j of (c + j + 1)
ROWS = 16 * 100 + 16 * 200 + 2 * (16 * 17 // 2)
KV_ROW = 2 * LAYERS * D * 2              # K and V, every layer, bf16


def test_decode_attn_roofline():
    read = reader("decode_attn_roofline.sat").read
    least = max(4 * D * LAYERS * ROWS / BF16, ROWS * KV_ROW / HBM)
    assert least == ROWS * KV_ROW / HBM          # bandwidth-bound
    tr = summary(ops=[
        ("pt_paged_attention_decode_inplace.1 f32[32,16,128]", least, 192),
        ("pt_paged_attention_decode_inplace.2 f32[32,16,128]", least, 192),
        ("pt_paged_attention_decode_inplace_q.1 f32[32,16,128]", 9.0, 1),
        ("closed_call.3 f32[32,16,128]", 9.0, 1)])
    assert read(ctx(tr, decode_facts())) == pytest.approx(50.0)
    # a program whose kernels have no names, and a run with no trace
    old = summary(ops=[("closed_call.3 f32[32,16,128]", 9.0, 1)])
    assert read(ctx(old, decode_facts())) is None
    assert read(ctx(None, decode_facts())) is None
    # no decode chunk inside the traced part
    nothing = dict(decode_facts(), steps=[(9, 9.5, "prefill")] * 3)
    assert read(ctx(tr, nothing)) is None


def test_decode_linear_roofline():
    read = reader("decode_linear_roofline.sat").read
    stacks = LAYERS * (4 * D * D + 2 * D * DFF) * 2     # bytes a device step
    need = 16 * stacks / HBM                            # one chunk of 16
    tr = summary(ops=[
        ("pt_stream_linear_layer_tail.1 (bf16[32,2048], bf16[32,6144])",
         need, 384),
        ("pt_stream_linear_bf16.4 bf16[32,6144]", need / 4, 16),
        ("pt_stream_linear_a8w8.1 bf16[32,6144]", 9.0, 1)])
    assert read(ctx(tr, decode_facts())) == pytest.approx(80.0)
    old = summary(ops=[("closed_call.9 (bf16[32,2048], bf16[32,6144])",
                        need, 384)])
    assert read(ctx(old, decode_facts())) is None
    assert read(ctx(None, decode_facts())) is None


def prefill_facts():
    """A request of 300 tokens: a chunk of 256 at position 0 and a tail of
    44 (padded to 64) behind it, both inside the traced part; one more
    chunk before the trace began."""
    r = Req(0, np.zeros(300, np.int32), 8)
    r.rid = 5
    journal = [
        {"ev": "prefill_chunk", "rid": 5, "ts": 9.0, "n": 256, "pos": 256},
        {"ev": "prefill_chunk", "rid": 5, "ts": 11.0, "n": 256, "pos": 256},
        {"ev": "prefill_chunk", "rid": 5, "ts": 12.0, "n": 44, "pos": 300},
        {"ev": "decode", "rid": 5, "ts": 12.0}]
    return {"requests": [r], "steps": [], "journal": journal}


def test_prefill_attn_roofline():
    read = reader("prefill_attn_roofline.chat").read
    pairs = 256 * 257 // 2 + (44 * 256 + 44 * 45 // 2)
    need = 4 * D * LAYERS * pairs / BF16
    tr = summary(ops=[("pt_flash_varlen_paged.1 f32[1,16,256,128]",
                       need * 4, 48)])
    assert read(ctx(tr, prefill_facts())) == pytest.approx(25.0)
    old = summary(ops=[("closed_call.2 f32[1,16,256,128]", need * 4, 48)])
    assert read(ctx(old, prefill_facts())) is None
    assert read(ctx(None, prefill_facts())) is None
    assert read(ctx(tr, dict(prefill_facts(), journal=[]))) is None


def test_train_attn_roofline():
    read = reader("train_attn_roofline").read
    facts = {"tokens_per_step": 2 * 2048, "seq": 2048}
    forward = 4 * D * LAYERS * (2048 * 2049 // 2) * 2      # two rows a step
    need = 3 * forward * 5 / BF16                          # five steps
    bwd = ("flash_mha_bwd_dq_block_q_major_256 bf16[2,16,2048,128]",
           need, 120)
    mods = [("jit__pure_step(123)", 1.6, 5)]
    tr = summary(ops=[("pt_flash_mha_fwd.3 bf16[2,16,2048,128]", need, 120),
                      bwd], modules=mods)
    assert read(ctx(tr, facts)) == pytest.approx(50.0)
    # the parent: JAX names the backward kernels, nothing names the forward
    assert read(ctx(summary(ops=[bwd], modules=mods), facts)) is None
    assert read(ctx(summary(ops=[bwd]), facts)) is None
    assert read(ctx(None, facts)) is None


def hole_facts(hole_s):
    """An open loop of 4 requests/s due from t = 10 (the traced part is
    10..18) to t = 30, each admitted 40 ms + a tenth of its index after it
    was due and given 9 tokens 30 ms apart (33 for every other one, request
    31's last ones past t = 18); ``hole_s`` seconds in which nothing steps
    follow the traced part (``Tracer.stop()``), so whatever was due or
    decoding in the hole waits for its end."""
    reqs = []
    # two more are due during the loop's last step before the stop (it
    # begins at 17.9): the loop first sees them after the hole
    dues = [10.0 + i / 4.0 for i in range(80)] + [17.93, 17.97]
    for i, due in enumerate(dues):
        r = Req(i, np.zeros(64, np.int32), 9)
        r.due = due
        r.state = "ok"
        gap = 0.030 if i % 2 else 0.033
        # admitted by the first step that begins once it is due
        first = (r.due if r.due < 17.9 else 18.0) + 0.050 + i * 1e-4
        r.token_t = [first + gap * k for k in range(9)]
        if hole_s and 18.0 <= r.token_t[-1] < 18.0 + hole_s:
            # stalled by the profiler: the tokens still owed come after it
            r.token_t = [t if t < 18.0 else 18.0 + hole_s + (t - 18.0)
                         for t in r.token_t]
        r.admitted = r.token_t[0] - 0.010
        r.done_t = r.token_t[-1]
        reqs.append(r)
    steps = [(9.0 + k / 10.0, 9.1 + k / 10.0, "decode") for k in range(90)]
    steps += [(18.0 + hole_s + k / 10.0, 18.1 + hole_s + k / 10.0, "decode")
              for k in range(150)]
    return {"requests": reqs, "due": reqs, "steps": steps}


@pytest.mark.parametrize("name,want", [
    # 32 requests due in the traced part and seen there (not the two due
    # during its last step): rank 31 waited 40 + 3.0 ms
    ("queue_wait_p95_ms.chat", 40.0 + 30 * 0.1),
    # 31 finished inside it (request 31's tokens run past its end):
    # fifteen at 30 ms a token, sixteen at 33; the median is the 16th
    ("tpot_p50_ms.sat", 33.0)])
def test_host_clock_readers_leave_out_the_profilers_hole(name, want):
    read = reader(name).read
    without, with_hole = hole_facts(0.0), hole_facts(20.0)
    assert read(ctx(None, without)) == pytest.approx(want)
    assert read(ctx(None, with_hole)) == pytest.approx(want)
    # the hole is in the facts: over every request it reads seconds
    stalled = [r for r in with_hole["requests"]
               if r.admitted - r.due > 1.0 or r.done_t - r.token_t[0] > 1.0]
    assert len(stalled) == 51
    # no traced part (a run without the profiler): nothing to read
    assert read(dict(ctx(None, without), traced=(None, None))) is None


PROGRAMS = {"PREFILL_PROGRAM": ("jit__chunk_prefill_fn(77)",
                                "jit_pt_fused_prefill_chunk(77)"),
            "DECODE_PROGRAM": ("jit__unknown(5)",
                               "jit__pt_fused_decode_chunk"),
            "TRAIN_PROGRAM": ("jit__pure_step(123)",
                              "jit_pt_train_step(9)")}
NEAR_MISSES = ["jit__chunk_prefill_fn_v2(77)", "jit__unknown_1(5)",
               "jit__pure_step2(123)", "jit_pt_fused_prefill_chunks(7)",
               "jit_pt_fused_decode_chunk_q(5)", "jit_pt_train_stepper(9)",
               "jit_pt_hybrid_prefill_chunk(99)", "xjit__unknown(5)",
               "pt_fused_decode_chunk(5)"]


@pytest.mark.parametrize("const", sorted(PROGRAMS))
def test_program_patterns_take_two_spellings_and_no_near_miss(const):
    """Today's module name and the ONE declared name each pattern accepts
    (the form ``readers_granite.py`` matches) read alike; the other
    programs' names and near misses read nothing."""
    from benchmark import readers

    pattern = getattr(readers, const)
    for name in PROGRAMS[const]:
        tr = summary(modules=[(name, 1.5, 3)]
                     + [(miss, 9.0, 1) for miss in NEAR_MISSES])
        assert readers.module_time(ctx(tr, {}), pattern) == (1.5, 3)
    others = [n for k, pair in PROGRAMS.items() if k != const for n in pair]
    tr = summary(modules=[(n, 9.0, 1) for n in NEAR_MISSES + others])
    assert readers.module_time(ctx(tr, {}), pattern) is None


@pytest.mark.parametrize("name,const,per", [
    ("prefill_chunk_ms.chat", "PREFILL_PROGRAM", 1),
    ("decode_step_ms.sat", "DECODE_PROGRAM", 16),
    ("train_step_device_ms", "TRAIN_PROGRAM", 1)])
def test_a_declared_program_name_reads_as_todays(name, const, per):
    """A reader of each program through its file: 12 ms a call under
    today's name, the same under the declared one, None under a
    stranger's."""
    read = reader(name).read
    for mod in PROGRAMS[const]:
        tr = summary(modules=[(mod, 0.048, 4)])
        assert read(ctx(tr, decode_facts())) == pytest.approx(12.0 / per)
    tr = summary(modules=[("jit_pt_hybrid_decode_chunk(1)", 0.048, 4)])
    assert read(ctx(tr, decode_facts())) is None


def hists(**totals):
    return ({}, {}, {"serve.step." + k: v for k, v in totals.items()})


@pytest.mark.parametrize("name", ["step_host_ms.sat", "step_host_ms.chat"])
def test_step_host_ms(name):
    read = reader(name).read
    facts = {"stats0": hists(total_ms=(10, 1000.0), run_ms=(8, 900.0)),
             "stats1": hists(total_ms=(30, 3500.0), run_ms=(26, 3300.0))}
    # 20 steps gained 2,500 ms in all and 2,400 ms of run: 5 ms a step
    assert read(ctx(None, facts)) == pytest.approx(5.0)
    old = {"stats0": hists(total_ms=(10, 1000.0)),
           "stats1": hists(total_ms=(30, 3500.0))}
    assert read(ctx(None, old)) is None
    assert read(ctx(None, {"stats0": None, "stats1": None})) is None


@pytest.mark.parametrize("phase", ["args", "dispatch", "rebind"])
def test_train_phase_ms(phase):
    from paddle_tpu.profiler import stats

    read = reader(f"train_{phase}_ms").read
    stats.enable()
    stats.reset()
    try:
        assert read(ctx(None, {})) is None          # nothing observed yet
        for v in (900.0, 7.0, 5.0, 6.0, 8.0):       # a compiling call first
            stats.observe(f"jit.train_step.{phase}_ms", v)
        assert read(ctx(None, {})) == 7.0
    finally:
        stats.reset()


def test_rehearsal_prints_both_step_host_ms(tmp_path, monkeypatch):
    """A toy checkout (bench_toy renames the real metrics' cells to its
    own) run on the CPU through the code path a chip run takes."""
    root = bench_toy.make_root(tmp_path)
    monkeypatch.setattr(harness.Tracer, "summary", lambda self, n: None)
    for cell, metric in (("toy-gpt.toy-closed", "step_host_ms.sat"),
                         ("toy-gpt.toy-open", "step_host_ms.chat")):
        listed = [m["name"] for m in harness.Cell(root, cell).per_layer]
        assert metric in listed
        out = io.StringIO()
        harness.run_cell(root, cell, 2 ** 31 + 5, 2.0, True,
                         need_chip=False, out=out, err=io.StringIO())
        got = json.loads(out.getvalue().strip().splitlines()[-1])["metrics"]
        assert got[metric]["unit"] == "ms" and got[metric]["value"] > 0
        # the same steps: never under what the old attribution calls host
        if metric.endswith(".sat"):
            assert got[metric]["value"] >= got["sched_host_ms.sat"]["value"]
