"""The device's idle split by the program's own spans
(``benchmark/idle_split.py``): the four rules on synthetic traces, the
old reduction untouched by the program's spans, the stepping line found
among the lines a real CPU profiler trace writes, and the idle a step by
phase."""
import glob
import threading
import time
from types import SimpleNamespace as NS

import numpy as np
import pytest

import bench_toy  # noqa: F401  (puts the repo on sys.path)
from benchmark import idle_split as isp
from benchmark import trace_reduce as tr

MS = 1_000_000


def ev(name, start_ms, dur_ms, **stats):
    return NS(name=name, start_ns=int(start_ms * MS),
              duration_ns=int(dur_ms * MS), stats=list(stats.items()))


def device(ops, mods, n=0):
    return NS(name=f"/device:TPU:{n}", lines=[
        NS(name="XLA Ops", events=[ev(f"fusion.{i}", s, d)
                                   for i, (s, d) in enumerate(ops)]),
        NS(name="XLA Modules", events=[ev(f"jit_p({i})", s, d)
                                       for i, (s, d) in enumerate(mods)])])


def step(s, action, plan, run, program, emit):
    """A serving step's spans: the step from ``s``, its phases as
    (start, dur) pairs, the program inside ``run``."""
    out = [ev("pt.serve.step", s, emit[0] + emit[1] - s + 2, action=action,
              step=1),
           ev("pt.serve.admit", s, plan[0] - s),
           ev("pt.serve.plan", *plan), ev("pt.serve.run", *run),
           ev("pt.program:decode", *program), ev("pt.serve.emit", *emit)]
    return out


def synthetic():
    """Window 0..100 ms, one device. Stepping line: a decode step 0-50
    inside ``bench.engine.step`` 0-51, ``bench.idle_wait`` 60-90. Another
    line sleeps in 50 ms pieces to 95, and submits 20-22. The device runs
    one program 14-36 whose operations cover 15-35, and a second 92-98
    whose operations cover 92-98."""
    main = [ev("bench.traced_window", 0, 100), ev("bench.engine.step", 0, 51),
            *step(0, "decode", plan=(2, 8), run=(10, 30), program=(12, 26),
                  emit=(40, 8)),
            ev("bench.idle_wait", 60, 30)]
    gen = [ev("bench.gen_sleep", 0, 50), ev("bench.gen_sleep", 50, 45),
           ev("bench.submit", 20, 2)]
    host = NS(name="/host:CPU", lines=[NS(name="python", events=main),
                                       NS(name="python", events=gen)])
    return NS(planes=[host, device([(15, 20), (92, 6)], [(14, 22), (92, 6)])])


def test_the_four_rules_by_hand():
    s = isp.split(synthetic())
    got = {k: round(v * 1e3, 9) for k, v in s.idle_by_span.items()}
    assert got == {
        # (a) inside a running program's interval
        "in_program": 1 + 1,
        # (b) the innermost span on the stepping line, keyed by action
        "pt.serve.admit[decode]": 2, "pt.serve.plan[decode]": 8,
        "pt.serve.run[decode]": 2 + 2, "pt.program:decode[decode]": 2 + 2,
        "pt.serve.emit[decode]": 8, "pt.serve.step[decode]": 2,
        "bench.engine.step": 1, "bench.idle_wait": 30,
        # (c) a harness span on another line: the sleeper, never a piece
        # the stepping line covers
        "bench.gen_sleep": 9 + 2,
        # (d) nothing open
        "unattributed": 2}
    assert s.stepping_line == "/host:CPU/0:python"
    assert s.span_n == 1 and s.spans_in_window == 6


def test_pieces_sum_to_window_less_busy():
    s = isp.split(synthetic())
    assert s.window_s == pytest.approx(0.100)
    assert s.busy_s == pytest.approx(0.026)
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s, abs=1e-12)


def test_a_sleeping_generator_takes_no_piece_of_a_step():
    """The old rule gives the pieces of the step that fall after the
    sleeper's start to the sleeper; the split gives it only the pieces
    where the stepping line has nothing open."""
    old = tr.reduce(synthetic())
    s = isp.split(synthetic())
    assert old.gap_s["gen_sleep"] > s.idle_by_span["bench.gen_sleep"]
    assert s.idle_by_span["bench.gen_sleep"] == pytest.approx(0.011)
    assert "bench.submit" not in s.idle_by_span


def test_action_keys_of_a_prefill_and_a_decode_step():
    main = [ev("bench.traced_window", 0, 100),
            *step(0, "prefill", plan=(1, 4), run=(5, 20), program=(6, 18),
                  emit=(25, 5)),
            *step(40, "decode", plan=(41, 4), run=(45, 20), program=(46, 18),
                  emit=(65, 5))]
    data = NS(planes=[NS(name="/host:CPU", lines=[NS(name="m", events=main)]),
                      device([(7, 16), (47, 16)], [])])
    s = isp.split(data)
    for action in ("prefill", "decode"):
        assert s.idle_by_span[f"pt.serve.plan[{action}]"] == \
            pytest.approx(0.004)
        assert s.idle_by_span[f"pt.serve.emit[{action}]"] == \
            pytest.approx(0.005)
        assert s.idle_by_span[f"pt.program:decode[{action}]"] == \
            pytest.approx(0.002)
    assert s.span_n == 2 and s.steps == {"prefill": 1, "decode": 1}


def test_a_step_that_began_before_many_spans_still_takes_its_gap():
    """100 short program spans between the step's start and a gap at its
    end: the old rule reads only the 64 spans before a gap's end."""
    main = [ev("bench.traced_window", 0, 100),
            ev("pt.serve.step", 0, 100, action="decode")]
    main += [ev("pt.program:x", 0.5 * i, 0.25) for i in range(100)]
    main += [ev("bench.engine.step", -1, 102)]
    main += [ev("bench.cb", 0.5 * i + 0.3, 0.1) for i in range(100)]
    data = NS(planes=[NS(name="/host:CPU", lines=[NS(name="m", events=main)]),
                      device([(0, 90)], [])])
    s = isp.split(data)
    assert s.idle_by_span["pt.serve.step[decode]"] == pytest.approx(0.010)
    assert tr.reduce(data).gap_s["unattributed"] == pytest.approx(0.010)


def test_gap_between_two_programs_is_not_in_program():
    main = [ev("bench.traced_window", 0, 20),
            ev("pt.serve.step", 0, 20, action="decode"),
            ev("pt.serve.run", 0, 20)]
    data = NS(planes=[NS(name="/host:CPU", lines=[NS(name="m", events=main)]),
                      device([(0, 5), (12, 4)], [(0, 8), (10, 8)])])
    s = isp.split(data)
    assert s.idle_by_span["in_program"] == pytest.approx(0.003 + 0.002
                                                         + 0.002)
    assert s.idle_by_span["pt.serve.run[decode]"] == pytest.approx(0.004)
    assert s.per_step_ms("program") == pytest.approx(7.0)


def test_two_devices_average_their_pieces():
    main = [ev("bench.traced_window", 0, 10),
            ev("pt.train.step", 0, 10, step_num=1),
            ev("pt.serve.plan", 0, 10)]
    data = NS(planes=[NS(name="/host:CPU", lines=[NS(name="m", events=main)]),
                      device([(0, 10)], [], n=1), device([(0, 6)], [])])
    s = isp.split(data, n_devices=2)
    assert s.busy_s == pytest.approx(0.008)
    assert s.idle_by_span["pt.serve.plan"] == pytest.approx(0.002)
    assert isp.split(data).idle_by_span["pt.serve.plan"] == \
        pytest.approx(0.004)                      # the first device alone


def test_innermost_of_spans_that_do_not_nest():
    got = isp.innermost([(0, 10, "a"), (5, 15, "b"), (5, 7, "c"),
                         (20, 20, "empty")])
    assert got == [(0, 5, "a"), (5, 7, "c"), (7, 15, "b")]


def test_without_program_spans_the_split_is_the_old_attribution():
    """A trace with harness spans alone and no program interval: rule (c)
    is today's rule, piece for piece."""
    data = synthetic()
    host = data.planes[0]
    for line in host.lines:
        line.events = [e for e in line.events
                       if not e.name.startswith("pt.")]
    data.planes[1] = device([(15, 20), (92, 6)], [])
    s = isp.split(data)
    old = tr.reduce(data)
    assert s.stepping_line is None
    want = {("bench." + k if k != "unattributed" else k): v
            for k, v in old.gap_s.items()}
    assert set(s.idle_by_span) == set(want)
    for k, v in want.items():
        assert s.idle_by_span[k] == pytest.approx(v, abs=1e-12)


def test_the_old_reduction_reads_the_same_with_program_spans():
    """What every per-layer metric reads today comes out the same from a
    trace with and without the program's spans in it."""
    with_pt = tr.reduce(synthetic())
    data = synthetic()
    for line in data.planes[0].lines:
        line.events = [e for e in line.events
                       if not e.name.startswith("pt.")]
    without = tr.reduce(data)
    for field in ("gap_s", "span_s", "op_s", "op_n", "module_s",
                  "module_n"):
        assert dict(getattr(with_pt, field)) == dict(getattr(without, field))
    assert (with_pt.busy_s, with_pt.window_s, with_pt.window_ns) == \
        (without.busy_s, without.window_s, without.window_ns)
    assert with_pt.busy_s == isp.split(synthetic()).busy_s


KEYS = {"plan": ["pt.serve.plan"],
        "run": ["pt.serve.run", "pt.program:serve.prefill[c=8]",
                "pt.program:jit_pt_hybrid_decode_chunk"],
        "emit": ["pt.serve.emit"], "program": ["in_program"],
        "other": ["pt.serve.admit", "pt.serve.step", "bench.engine.step",
                  "unattributed"]}


@pytest.mark.parametrize("action,ms", [("prefill", 3), ("decode", 1)])
@pytest.mark.parametrize("phase", ["plan", "run", "emit", "program"])
def test_idle_a_step_by_phase(phase, action, ms):
    """Each phase reads its own keys and nothing else: 3 ms a key of the
    prefill steps, 1 ms of the decode steps, two steps each; the pieces
    inside programs and the harness's carry no action (4 ms a key)."""
    s = isp.IdleSplit()
    s.steps.update(prefill=2, decode=2)
    for k in (k for ks in KEYS.values() for k in ks):
        if k.startswith("pt."):
            s.idle_by_span[f"{k}[prefill]"] = 0.003
            s.idle_by_span[f"{k}[decode]"] = 0.001
        else:
            s.idle_by_span[k] = 0.004
    n = len(KEYS[phase])
    assert s.per_step_ms(phase) == pytest.approx(n * 4 / 4)
    assert s.per_step_ms(phase, action) == pytest.approx(
        0.0 if phase == "program" else n * ms / 2)
    assert s.per_step_ms(phase, "verify") is None
    s.steps.clear()
    assert s.per_step_ms(phase) is None


def test_real_cpu_trace_finds_the_stepping_line(tmp_path):
    """The toy engine stepped on this thread while another emits harness
    spans; a device plane laid on the trace's own clock runs each program
    span's middle half. The stepping line is found among the lines the
    profiler wrote, and no sleeper's piece falls inside a step."""
    import jax
    from jax.profiler import ProfileData

    import paddle_tpu as paddle
    from paddle_tpu.inference import FusedCausalLM
    from paddle_tpu.serving import ServingEngine, SLOConfig

    paddle.seed(7)
    model = FusedCausalLM(vocab_size=64, embed_dim=32, num_heads=4,
                          dim_feedforward=64, num_layers=2, max_position=256)
    eng = ServingEngine(model, max_batch=2, page_size=4, max_length=96,
                        decode_chunk=2, slo=SLOConfig(prefill_chunk=8))
    rng = np.random.RandomState(0)
    eng.submit(rng.randint(0, 64, (6,)), max_new_tokens=4)
    eng.run()                           # compiled before the session
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    stop = threading.Event()

    def sleeper():
        while not stop.is_set():
            with jax.profiler.TraceAnnotation("bench.gen_sleep"):
                time.sleep(0.002)

    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    thread = threading.Thread(target=sleeper, daemon=True)
    thread.start()
    try:
        with jax.profiler.TraceAnnotation("bench.traced_window"):
            for n in (10, 14):
                eng.submit(rng.randint(0, 64, (n,)), max_new_tokens=4)
            while eng.has_work:
                with jax.profiler.TraceAnnotation("bench.engine.step"):
                    eng.step()
    finally:
        stop.set()
        thread.join(timeout=10)
        jax.profiler.stop_trace()
    assert not thread.is_alive()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    real = ProfileData.from_file(path)
    planes = list(real.planes)
    spans, stepping = [], set()
    for p in planes:
        for n, line in enumerate(p.lines):
            for e in line.events:
                if e.name.startswith(("pt.", "bench.")):
                    spans.append((e.name, int(e.start_ns),
                                  int(e.duration_ns)))
                if e.name == "pt.serve.step":
                    stepping.add(f"{p.name}/{n}:{line.name}")
    programs = [(s, d) for n, s, d in spans if n.startswith("pt.program:")]
    assert programs
    ops = [(s + d // 4, d // 2) for s, d in programs]
    plane = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[NS(name="op", start_ns=s, duration_ns=d,
                                      stats=[]) for s, d in ops]),
        NS(name="XLA Modules", events=[NS(name="jit_p", start_ns=s,
                                          duration_ns=d, stats=[])
                                       for s, d in programs])])
    s = isp.split(NS(planes=planes + [plane]))

    steps = [sp for sp in spans if sp[0] == "pt.serve.step"]
    assert [s.stepping_line] == list(stepping)
    assert s.span_n == len(steps) == sum(s.steps.values())
    assert set(s.steps) == {"prefill", "decode"}
    assert {k.rsplit("[", 1)[1] for k in s.idle_by_span if "[" in k} == \
        {"prefill]", "decode]"}
    for key in ("pt.serve.plan[prefill]", "pt.serve.emit[decode]",
                "pt.serve.step[decode]", "in_program"):
        assert s.idle_by_span[key] > 0
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s, abs=1e-9)
    # the sleeper: at most what no step covers
    win, = [(st, st + d) for n, st, d in spans if n == "bench.traced_window"]
    in_steps = sum(min(st + d, win[1]) - max(st, win[0])
                   for _, st, d in steps)
    outside = (win[1] - win[0] - in_steps) / 1e9
    assert 0 < s.idle_by_span["bench.gen_sleep"] <= outside
