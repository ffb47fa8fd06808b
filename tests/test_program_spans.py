"""The program's spans (``profiler.RecordEvent``): that they land in the
``jax.profiler`` trace nested as the work nests, that the step histograms
come from the same stamps and partition the step exactly, that a span
costs nothing where nobody listens, and that every Pallas launch and
every hot program carries the name the device trace is read by."""
import glob
import re

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.analysis.sites import (KERNEL_PREFIX, KERNEL_SITES,
                                       _force_tpu_routing, trace_site)
from paddle_tpu.inference import FusedCausalLM
from paddle_tpu.profiler import (RecordEvent, profiler, start_span_capture,
                                 stats, stop_span_capture)
from paddle_tpu.serving import ServingEngine, SLOConfig
from paddle_tpu.serving.faults import ManualClock, use_clock


@pytest.fixture(autouse=True)
def _clean_registry():
    stats.enable()
    stats.reset()
    yield
    stats.reset()


def _engine(**kw):
    paddle.seed(7)
    model = FusedCausalLM(vocab_size=64, embed_dim=32, num_heads=4,
                          dim_feedforward=64, num_layers=2,
                          max_position=256)
    kw.setdefault("slo", SLOConfig(prefill_chunk=8))
    return ServingEngine(model, max_batch=2, page_size=4, max_length=96,
                         decode_chunk=2, **kw)


def _train_step():
    import paddle_tpu.nn as nn

    paddle.seed(3)
    model = nn.Linear(8, 4)
    opt = paddle.optimizer.SGD(0.1, parameters=model.parameters())
    step = paddle.jit.TrainStep(
        model, lambda out, lbl: ((out - lbl) ** 2).mean(), opt)
    inp = paddle.to_tensor(np.ones((2, 8), np.float32))
    lbl = paddle.to_tensor(np.zeros((2, 4), np.float32))
    return step, inp, lbl


def _prompts():
    rng = np.random.RandomState(0)
    return [rng.randint(0, 64, (n,)) for n in (6, 10, 14)]


# ------------------------------------------------- (a) the profiler's trace

def _traced_spans(tmp_path, work):
    """Run ``work`` under a jax.profiler session; the program's spans of
    the trace as (name, start_ns, end_ns, ids), in start order."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(profiler.SPAN_PREFIX):
                    s = int(ev.start_ns)
                    spans.append((ev.name, s, s + int(ev.duration_ns),
                                  dict(ev.stats)))
    return sorted(spans, key=lambda e: (e[1], -e[2]))


def _inside(spans, outer):
    return [e for e in spans
            if e is not outer and outer[1] <= e[1] and e[2] <= outer[2]]


def test_serving_spans_nest_in_the_profilers_trace(tmp_path):
    eng = _engine()
    eng.submit(_prompts()[0], max_new_tokens=4)
    eng.run()                           # compiled before the session

    def work():
        for p in _prompts()[1:]:
            eng.submit(p, max_new_tokens=4)
        eng.run()

    spans = _traced_spans(tmp_path, work)
    steps = [e for e in spans if e[0] == "pt.serve.step"]
    assert steps and [e[3]["step"] for e in steps] \
        == sorted(e[3]["step"] for e in steps)
    assert {e[3]["action"] for e in steps} == {"prefill", "decode"}
    ran = 0
    for step in steps:
        inner = _inside(spans, step)
        names = [e[0] for e in inner if e[0].startswith("pt.serve.")]
        if "pt.serve.run" not in names:
            continue                    # a deferred chunk: admit + plan
        ran += 1
        assert names == ["pt.serve.admit", "pt.serve.plan", "pt.serve.run",
                         "pt.serve.emit"]
        phases = [e for e in inner if e[0].startswith("pt.serve.")]
        assert all(a[2] <= b[1] for a, b in zip(phases, phases[1:]))
        run = phases[2]
        program, = [e for e in _inside(spans, run)
                    if e[0].startswith("pt.program:")]
        assert program[0] == "pt.program:" + run[3]["program"]
        if step[3]["action"] == "prefill":
            assert "rid" in run[3]
    assert ran >= 2


def test_train_spans_nest_in_the_profilers_trace(tmp_path):
    step, inp, lbl = _train_step()
    step([inp], [lbl])                  # compiled before the session
    spans = _traced_spans(tmp_path, lambda: [step([inp], [lbl])
                                             for _ in range(3)])
    calls = [e for e in spans if e[0] == "pt.train.step"]
    assert [e[3]["step_num"] for e in calls] == [1, 2, 3]
    for call in calls:
        inner = _inside(spans, call)
        assert [e[0] for e in inner if e[0].startswith("pt.train.")] \
            == ["pt.train.args", "pt.train.dispatch", "pt.train.rebind"]
        dispatch = inner[1]
        program, = [e for e in _inside(spans, dispatch)]
        assert program[0] == "pt.program:TrainStep[Linear]" \
            == "pt.program:" + dispatch[3]["program"]
    h = stats.snapshot(prefix="jit.train_step.")["histograms"]
    assert {n: v["count"] for n, v in h.items()} == {
        "jit.train_step.args_ms": 4, "jit.train_step.dispatch_ms": 4,
        "jit.train_step.rebind_ms": 4}


# ------------------------------------- (b) histograms from the same stamps

class _SteppingClock(ManualClock):
    """Every reading is a quarter of a second after the last: each phase
    of a step gets a length of its own that floats hold exactly."""

    def now(self):
        return self.advance(0.25)


def test_step_phases_partition_the_step_exactly():
    eng = _engine()
    with use_clock(_SteppingClock()):
        for p in _prompts():
            eng.submit(p, max_new_tokens=6)
        eng.run()
    h = stats.snapshot(prefix="serve.step.")["histograms"]
    t = {n.rsplit(".", 1)[1]: v["total"] for n, v in h.items()}
    n = {n.rsplit(".", 1)[1]: v["count"] for n, v in h.items()}
    assert t["admit_ms"] + t["plan_ms"] + t["run_ms"] + t["emit_ms"] \
        + t["host_overhead_ms"] == t["total_ms"]
    assert t["prefill_chunk_ms"] + t["decode_chunk_ms"] \
        == t["plan_ms"] + t["run_ms"] + t["emit_ms"]
    assert n["plan_ms"] == n["run_ms"] == n["emit_ms"] \
        == n["prefill_chunk_ms"] + n["decode_chunk_ms"]
    assert t["run_ms"] > 0 and t["plan_ms"] > 0 and t["emit_ms"] > 0


def test_one_prefill_chunk_is_plan_plus_run_plus_emit():
    eng = _engine()
    with use_clock(_SteppingClock()):
        eng.submit(_prompts()[0], max_new_tokens=1)   # one chunk, one token
        eng.run()
    h = stats.snapshot(prefix="serve.step.")["histograms"]
    assert h["serve.step.prefill_chunk_ms"]["count"] == 1
    assert h["serve.step.prefill_chunk_ms"]["total"] \
        == h["serve.step.plan_ms"]["total"] \
        + h["serve.step.run_ms"]["total"] \
        + h["serve.step.emit_ms"]["total"]
    # ts_admit | run start | run end: a quarter-second each; the emit
    # phase reads the clock for its own marks (first token, done)
    assert h["serve.step.plan_ms"]["total"] == 250.0
    assert h["serve.step.run_ms"]["total"] == 250.0
    assert h["serve.step.emit_ms"]["total"] >= 250.0


def test_speculative_round_is_booked_as_run():
    eng = _engine(speculative="self", spec_k=3)
    with use_clock(_SteppingClock()):
        for p in _prompts():
            eng.submit(p, max_new_tokens=6)
        eng.run()
    h = stats.snapshot(prefix="serve.step.")["histograms"]
    t = {n.rsplit(".", 1)[1]: v["total"] for n, v in h.items()}
    assert t["spec_verify_ms"] > 0 and "decode_chunk_ms" not in t
    assert t["admit_ms"] + t["plan_ms"] + t["run_ms"] + t["emit_ms"] \
        + t["host_overhead_ms"] == t["total_ms"]
    assert t["prefill_chunk_ms"] + t["spec_verify_ms"] \
        == t["plan_ms"] + t["run_ms"] + t["emit_ms"]


# ------------------------------------------ (c) a span nobody listens to

def test_span_appends_nothing_without_session_or_sink():
    assert not profiler._SPANS.enabled and not profiler._SINKS
    before = list(profiler._SPANS.events)
    with RecordEvent("quiet", rid=1) as ev:
        pass
    assert profiler._SPANS.events == before and not profiler._SINKS
    assert ev.dur_ms >= 0


def test_sink_gets_the_bare_name_and_the_ids():
    sink = start_span_capture()
    try:
        with RecordEvent("heard", rid=7) as ev:
            ev.annotate(action="decode")
    finally:
        stop_span_capture(sink)
    got, = sink
    assert got["name"] == "heard" and got["ph"] == "X"
    assert got["args"] == {"rid": 7, "action": "decode"}
    assert got["dur"] == pytest.approx(ev.dur_ms * 1e3)


# -------------------------------------------------- (d) the kernels' names

@pytest.mark.parametrize("site", KERNEL_SITES, ids=lambda s: s.name)
def test_every_launch_carries_its_trace_name(site):
    """``name=`` of the ``pallas_call`` and the ``jax.named_scope`` around
    it are the table's name (the compiled instruction, which the device
    trace shows, takes the innermost of the two); JAX's own flash forward
    has only the scope this repo opens around it."""
    records = trace_site(site)
    assert len(site.kernels) == site.n_calls
    assert all(k.startswith(KERNEL_PREFIX) for k in site.kernels)
    if "jax/experimental" in records[0].path:
        # JAX traces its kernel inside a custom_vjp of its own, where the
        # shim sees no outer scope: the scope is on the call's equation
        # (tests/test_chip_compile.py sees it on the compiled instruction)
        fn, args = site.build()
        with _force_tpu_routing():
            closed = jax.make_jaxpr(fn)(*args)
        assert site.kernels[0] in _scopes_over(closed.jaxpr, "pallas_call")
        return
    for rec, want in zip(records, site.kernels):
        assert rec.name == rec.scope == want


def _scopes_over(jaxpr, primitive, outer=()):
    """The name-stack entries of every equation above a ``primitive``."""
    found = []
    for eqn in jaxpr.eqns:
        here = outer + (str(eqn.source_info.name_stack),)
        if eqn.primitive.name == primitive:
            found += here
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _scopes_over(sub, primitive, here)
    return found


def test_trace_names_are_one_per_pallas_call():
    by_site = {}
    for site in KERNEL_SITES:
        for rec, name in zip(trace_site(site), site.kernels):
            by_site.setdefault((rec.path, rec.line), set()).add(name)
    assert all(len(names) == 1 for names in by_site.values())
    names = [next(iter(v)) for v in by_site.values()]
    assert len(set(names)) == len(names) == 19     # 18 of ours + JAX's


def test_no_pallas_call_without_a_name():
    import os

    here = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "paddle_tpu", "nn", "functional")
    calls = named = 0
    for path in glob.glob(os.path.join(here, "*.py")):
        src = open(path).read()
        for m in re.finditer(r"pl\.pallas_call\(\s*kernel,\s*(\w+)=", src):
            calls += 1
            named += m.group(1) == "name"
        assert len(re.findall(r"pallas_call\(", src)) \
            == len(re.findall(r"pl\.pallas_call\(\s*kernel,", src))
    assert calls == named == 18


# ------------------------------------------ (e) the programs' module names

def _module_name(jitted, *args):
    return re.search(r"module @(\S+)",
                     jitted.lower(*args).as_text()).group(1)


def test_module_names_the_benchmarks_readers_match():
    """``benchmark/readers.py`` finds the three programs in the device
    trace by the names XLA gives their modules: ``PREFILL_PROGRAM``
    (``^jit__chunk_prefill_fn\\(``: prefill_chunk_ms.chat,
    prefill_step_mfu.chat), ``DECODE_PROGRAM`` (``^jit__unknown\\(``:
    decode_step_ms.sat, decode_step_roofline.sat, decode_step_mfu.sat) and
    ``TRAIN_PROGRAM`` (``^jit__pure_step\\(``: train_step_device_ms,
    train_step_mfu, train_attn_bwd_roofline, train_attn_roofline). Renaming
    or re-wrapping a jitted function silences them; the stable names are
    on the host side, in ``pt.program:<name>``."""
    eng = _engine()
    seen = {}

    class Spy:
        def __init__(self, prog):
            self.name, self.real = prog.name, prog._jitted

        def lower(self, *args):
            seen[self.name] = _module_name(self.real, *args)
            return self.real.lower(*args)

    for prog in (eng._get_chunk_prefill(8), eng._gen._get_decode_k(8)):
        prog._jitted = Spy(prog)
    eng.decode_chunk = 8
    eng.submit(_prompts()[0], max_new_tokens=9)
    eng.run()
    assert seen == {"serve.prefill[c=8]": "jit__chunk_prefill_fn",
                    "decode.f32_grouped[k=8]": "jit__unknown"}
    step, inp, lbl = _train_step()
    assert _module_name(step._compiled.jitted,
                        *step._build_args([inp], [lbl])) == "jit__pure_step"
