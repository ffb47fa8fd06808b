#!/usr/bin/env python3
"""Where a cell's device idle goes, by the program's own spans, and what
those spans cost. Chip-side tools, not part of a run.

    cell       one traced window of a cell through its own ``Driver`` and
               the harness's ``Tracer`` (no reference check): the trace
               reduced as a run reduces it and split by
               ``benchmark/idle_split.py``, each timed; the idle a step
               under plan, run, emit and inside running programs. One JSON
               line on standard output
    span-cost  one ``profiler.RecordEvent`` begin/end pair in a loop, with
               a ``jax.profiler`` session open and with none

    python3 benchmark/tools/idle_trace.py cell --workload W --seed N
    python3 benchmark/tools/idle_trace.py span-cost --loops 100000
"""
import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def say(obj):
    print(json.dumps(obj), flush=True)


def report(summary, split):
    """What a traced window's two reductions say, side by side."""
    from benchmark import idle_split

    idle = split.window_s - split.busy_s
    per_step = {a or "all": {p: split.per_step_ms(p, a)
                             for p in idle_split.PHASES}
                for a in [None, *sorted(split.steps, key=str)]}
    return {
        "window_s": split.window_s, "busy_s": split.busy_s, "idle_s": idle,
        "idle_by_span_sum_s": sum(split.idle_by_span.values()),
        "busy_s_reduce": summary.busy_s,
        "unattributed_share": (split.idle_by_span.get(
            idle_split.UNATTRIBUTED, 0.0) / idle) if idle > 0 else None,
        "span_n": split.span_n, "steps": {str(a): n for a, n in
                                          split.steps.items()},
        "spans_per_step": (split.spans_in_window / split.span_n
                           if split.span_n else None),
        "idle_per_step_ms": 1e3 * idle / split.span_n if split.span_n
        else None,
        "per_step_ms": per_step,
        "idle_by_span": split.top(16),
        "gap_s": summary.top_gaps(10),
        "stepping_line": split.stepping_line,
    }


def cell(args):
    from jax.profiler import ProfileData

    from benchmark import idle_split, readers, trace_reduce

    c = harness.Cell(ROOT, args.workload)
    harness.require_chip(c.chips)
    tracer = harness.Tracer(True, os.path.join(ROOT, ".bench_trace"),
                            c.traffic.get("trace_s", 8.0))
    drv = c.driver().Driver(c, args.seed, args.seconds, tracer)
    drv.setup()
    drv.window()
    t0 = time.perf_counter()
    data = ProfileData.from_file(tracer.xplane_path())
    t1 = time.perf_counter()
    summary = trace_reduce.reduce(data, n_devices=c.chips,
                                  span_prefix=harness.TRACE_PREFIX)
    t2 = time.perf_counter()
    split = idle_split.split(data, n_devices=c.chips,
                             span_prefix=harness.TRACE_PREFIX)
    t3 = time.perf_counter()
    shutil.rmtree(tracer.dir, ignore_errors=True)
    facts = drv.facts()
    if tracer.stats_m1 is not None:
        facts["stats1"] = tracer.stats_m1
    total = readers.hist_delta(facts, "serve.step.total_ms")
    run = readers.hist_delta(facts, "serve.step.run_ms")
    rec = {"workload": args.workload, "seed": args.seed,
           "load_s": t1 - t0, "reduce_s": t2 - t1, "split_s": t3 - t2,
           "step_host_ms": ((total[1] - run[1]) / total[0]
                            if total and run and total[0] else None)}
    rec.update(report(summary, split))
    say(rec)


def span_cost(args):
    """Nanoseconds a RecordEvent begin/end pair, with no profiler session
    and inside one."""
    import jax

    from paddle_tpu.profiler import RecordEvent

    def loop():
        t0 = time.perf_counter_ns()
        for _ in range(args.loops):
            with RecordEvent("serve.plan"):
                pass
        return (time.perf_counter_ns() - t0) / args.loops

    loop()
    off = loop()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        on = loop()
    finally:
        jax.profiler.stop_trace()
    nbytes = sum(os.path.getsize(p) for p in glob.glob(
        os.path.join(tmp, "**", "*.xplane.pb"), recursive=True))
    shutil.rmtree(tmp, ignore_errors=True)
    say({"span_cost_ns_off": off, "span_cost_ns_on": on,
         "loops": args.loops, "xplane_bytes": nbytes,
         "platform": jax.devices()[0].platform})


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("cell", "span-cost"))
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=2 ** 31 + 11)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--loops", type=int, default=100_000)
    args = ap.parse_args(argv)
    harness.setup_environment(ROOT)
    if args.what == "cell":
        if not args.workload:
            ap.error("cell needs --workload")
        cell(args)
    else:
        span_cost(args)


if __name__ == "__main__":
    main()
