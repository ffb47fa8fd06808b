#!/usr/bin/env python3
"""Chip-side tools of the benchmark's builder, kept so that a later
benchmark PR can repeat them. None of them is part of a run.

    trace-dump  one short traced window of a cell; writes the planes, lines
                and the heaviest operation and program names (with their
                stats) to chiprun_out/, to be read by hand before a reader
                is written against them
    sweep       one engine, one window per arrival rate: completions, the
                waiting queue at the middle and at the end, the slots and
                pages held, TTFT and TPOT; the knee is the highest rate
                that keeps up
    gaps        the numbers ``correct`` compares, for the program and for
                the controls, over seeds, in one process
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

OUT = os.path.join(ROOT, "chiprun_out")


def say(obj):
    print(json.dumps(obj), flush=True)


def make_driver(workload, seed, seconds, trace=False, cap=8.0):
    cell = harness.Cell(ROOT, workload)
    tracer = harness.Tracer(trace, os.path.join(ROOT, ".bench_trace"), cap)
    return cell, cell.driver().Driver(cell, seed, seconds, tracer)


def trace_dump(args):
    from jax.profiler import ProfileData

    cell, drv = make_driver(args.workload, args.seeds[0], args.seconds,
                            True, args.seconds)
    drv.setup()
    drv.window()
    path = drv.tracer.xplane_path()
    data = ProfileData.from_file(path)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, f"trace_dump.{args.workload}.txt"), "w") as f:
        print("xplane bytes", os.path.getsize(path), file=f)
        for pl in data.planes:
            print("PLANE", pl.name, file=f)
            for ln in pl.lines:
                evs = list(ln.events)
                print("  LINE", ln.name, len(evs), file=f)
                agg = {}
                for ev in evs:
                    a = agg.setdefault(ev.name, [0, 0.0, None])
                    a[0] += 1
                    a[1] += ev.duration_ns
                    if a[2] is None:
                        a[2] = [(k, str(v)[:300]) for k, v in ev.stats]
                for name, (n, ns, st) in sorted(
                        agg.items(), key=lambda kv: -kv[1][1])[:60]:
                    print(f"    {ns / 1e6:12.3f} ms {n:8d} x {name[:120]}",
                          file=f)
                    if pl.name.startswith("/device"):
                        print(f"        stats {st}", file=f)
    summary = drv.tracer.summary(1)
    say({"window_s": summary.window_s, "busy_s": summary.busy_s,
         "top_ops": summary.top_ops(10), "top_gaps": summary.top_gaps(10),
         "modules": sorted(summary.module_s.items(),
                           key=lambda kv: -kv[1])[:10]})


def sweep(args):
    """One engine; a window per rate with a drain between."""
    cell, drv = make_driver(args.workload, args.seeds[0], args.seconds)
    drv.setup()
    engine, model = drv.engine, drv.model
    for rate in args.rates:
        cell, d = make_driver(args.workload, args.seeds[0], args.seconds)
        d.mix = dict(d.mix, arrivals=dict(d.mix["arrivals"], rate_rps=rate))
        d.engine, d.model = engine, model
        mid = {}
        orig = d._step

        def stepper(d=d, mid=mid, orig=orig):
            out = orig()
            now = time.monotonic()
            if d.t_open is not None and d.t_open <= now < d.t_close:
                # what could stop an admission: slots, pages, the queue
                eng, mgr = d.engine, d.engine._mgr
                held = eng.num_active + eng.num_prefilling
                dt = now - mid.get("t", now)
                mid["t"] = now
                mid["slot_s"] = mid.get("slot_s", 0.0) + held * dt
                mid["slots_max"] = max(mid.get("slots_max", 0), held)
                mid["pages"] = max(mid.get("pages", 0),
                                   mgr.num_pages - mgr.free_pages)
            if "q" not in mid and d.t_open is not None and \
                    now >= (d.t_open + d.t_close) / 2:
                mid["q"] = d.engine.queue_depth
                mid["done"] = sum(1 for r in d.reqs if r.state == "ok")
            if "q_end" not in mid and d.t_close is not None and \
                    now >= d.t_close - 0.2:
                mid["q_end"] = d.engine.queue_depth
            return out

        d._step = stepper
        d.window()
        f = d.facts()
        say({"rate_rps": rate, "attempted": f["attempted"],
             "failed": f["failed"], "queue_mid": mid.get("q"),
             "queue_end": mid.get("q_end"),
             "slots_mean": mid.get("slot_s", 0.0) / args.seconds,
             "slots_max": mid.get("slots_max"),
             "pages_max": mid.get("pages"),
             "ttft_p50_ms": f.get("ttft_p50_ms"),
             "ttft_p95_ms": f.get("ttft_p95_ms"),
             "tpot_p50_ms": f.get("tpot_p50_ms"),
             "tpot_p95_ms": f.get("tpot_p95_ms"),
             "serve_tok_s": f["serve_tok_s"],
             "drain_s": max(r.done_t or 0 for r in d.reqs) - d.t_close})
        # let the engine empty before the next rate
        while engine.has_work:
            engine.step()


def gaps(args):
    for seed in args.seeds:
        cell, drv = make_driver(args.workload, seed, args.seconds)
        t0 = time.monotonic()
        drv.setup()
        drv.window()
        drv.release()
        rec = {"seed": seed, "workload": args.workload}
        rec["program"] = {k: c["value"]
                          for k, c in drv.check()["compared"].items()}
        for mode in args.modes:
            rec[mode] = drv.control(mode)
        rec["seconds"] = time.monotonic() - t0
        say(rec)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("trace-dump", "sweep", "gaps"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", default=[])
    ap.add_argument("--modes", nargs="+", default=["int8", "fp8"])
    args = ap.parse_args()
    harness.setup_environment(ROOT)
    harness.require_chip(1)
    {"trace-dump": trace_dump, "sweep": sweep, "gaps": gaps}[args.what](args)


if __name__ == "__main__":
    main()
