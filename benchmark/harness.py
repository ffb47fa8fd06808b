"""The harness behind ``benchmark/run.py``: everything that is the same for
every cell. What belongs to one configuration, one traffic mix, one driver
or one per-layer metric is in a file of its own, found by the name that
``BENCHMARK.json`` (or the mix's file) gives:

    benchmark/configs/<config>.json      sizes, source, assumed, reduced
    benchmark/traffic/<traffic>.json     the mix's parameters and ``driver``
    benchmark/drivers/<driver>.py        ``Driver(cell, seed, seconds, tools)``
    benchmark/metrics/<metric>.py        ``read(ctx)`` -> number or None
    benchmark/kernels/<name>.py          operations and bytes from shapes
    benchmark/reference/<family>.py      the plain reference
    benchmark/peaks.json                 published peaks by device_kind

A run: look for the chip, set up (build, warm, lead in), open the window,
measure, close, read the memory peak, free the program, decide ``correct``
against the plain reference, reduce the trace, print one line.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time

T_PROCESS_START = time.monotonic()
TRACE_PREFIX = "bench."           # the harness's own spans in the trace


class NoChip(RuntimeError):
    pass


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


class Cell:
    """One entry of ``workloads`` with the files it names, read from
    ``root`` (the checkout; a test may point it at a copy)."""

    def __init__(self, root: str, workload: str):
        self.root = root
        bench = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"workload {workload!r} is not in BENCHMARK.json "
                           f"(known: {sorted(cells)})")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        self.bench_dir = os.path.join(root, bench["paths"][0])
        cfg_entry = {c["name"]: c for c in bench["configs"]}[
            self.entry["config"]]
        self.config = load_json(os.path.join(root, cfg_entry["file"]))
        self.traffic = load_json(os.path.join(
            self.bench_dir, "traffic", self.entry["traffic"] + ".json"))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if _applies(m, workload)]
        self.per_layer = [m for m in bench["per_layer"]
                          if _applies(m, workload)]

    def driver(self):
        name = self.traffic["driver"]
        return load_module(os.path.join(self.bench_dir, "drivers",
                                        name + ".py"), "bench_driver_" + name)

    def reader(self, metric: str):
        return load_module(
            os.path.join(self.bench_dir, "metrics", metric + ".py"),
            "bench_metric_" + metric.replace(".", "_").replace("-", "_"))

    def peaks(self, device_kind: str) -> dict:
        table = load_json(os.path.join(self.bench_dir, "peaks.json"))
        row = table["chips"].get(device_kind.lower())
        if row is None:
            raise KeyError(f"device_kind {device_kind!r} is not in "
                           f"benchmark/peaks.json: add its published peaks")
        return row


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chip(chips: int) -> dict:
    info = device_info()
    if info["platform"] == "cpu" or info["count"] < chips:
        raise NoChip(f"needs {chips} accelerator chip(s); JAX found {info}")
    return info


def memory_peak_bytes(n: int):
    """Peak of the runtime's ``peak_bytes_in_use`` over the chips used: live
    arrays; a program's temp is NOT in it (PERF.md, memory count)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:n]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Compiles:
    """JAX's own ``backend_compile_duration`` events: seconds compiled
    during set-up, and how many compiles fell inside the window."""

    def __init__(self):
        import jax

        self.events = []            # (monotonic time at end, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event.endswith("backend_compile_duration"):
            self.events.append((time.monotonic(), float(secs)))

    def seconds_before(self, t) -> float:
        return sum(s for at, s in self.events if at <= t)

    def count_between(self, t0, t1) -> int:
        return sum(1 for at, _ in self.events if t0 < at <= t1)


class Tracer:
    """The profiler around the first ``cap_s`` seconds of the window, in a
    run of its own (``--trace 1``). The harness's spans go into the same
    trace (``span``), so device gaps are attributed on one clock."""

    def __init__(self, on: bool, directory: str, cap_s: float):
        self.on, self.dir, self.cap_s = bool(on), directory, float(cap_s)
        self.active = False
        self.m0 = self.m1 = None
        self._ann = None
        self.stats_m1 = None        # the program's counters at ``m1``

    def start(self):
        if not self.on:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(TRACE_PREFIX + "traced_window")
        self._ann.__enter__()
        self.m0 = time.monotonic()
        self.active = True

    def poll(self):
        """Called between steps: stop once the traced part is long enough."""
        if self.active and time.monotonic() - self.m0 >= self.cap_s:
            self.stop()

    def stop(self):
        if not self.active:
            return
        import jax

        self.m1 = time.monotonic()
        self._ann.__exit__(None, None, None)
        # the stop holds the calling loop for tens of seconds: whatever a
        # reader takes from a traced run, it takes from before ``m1``
        self.stats_m1 = program_stats()
        jax.profiler.stop_trace()
        self.active = False

    def span(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(TRACE_PREFIX + name)

    def xplane_path(self) -> str:
        paths = []
        for base, _dirs, files in os.walk(self.dir):
            paths += [os.path.join(base, f) for f in files
                      if f.endswith(".xplane.pb")]
        if not paths:
            raise RuntimeError(f"the profiler wrote no .xplane.pb under "
                               f"{self.dir}")
        return sorted(paths)[-1]

    def summary(self, n_devices: int):
        """Reduce the trace that was written, then delete it."""
        if not self.on or self.m1 is None:
            return None
        from jax.profiler import ProfileData

        from benchmark import trace_reduce

        data = ProfileData.from_file(self.xplane_path())
        out = trace_reduce.reduce(data, n_devices=n_devices,
                                  span_prefix=TRACE_PREFIX)
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


def program_stats():
    """The program's counters, gauges and histogram totals as they stand:
    what a driver samples at the window's edges (``stats0`` / ``stats1``)."""
    from paddle_tpu.profiler import stats

    return stats.sample_values()


def setup_environment(root: str):
    """Before JAX is imported: the compile cache at a fixed path inside
    the checkout unless the environment already names one; ``BENCH_RUN`` is
    the driver's own and is not read."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(root, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if root not in sys.path:
        sys.path.insert(0, root)


def fmt_compared(compared: dict) -> str:
    return " ".join(f"{k}={v['value']:.6g}(limit {v['limit']:.6g})"
                    for k, v in compared.items())


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, need_chip: bool = True, out=None, err=None):
    """One run of one cell. Returns the result object it printed as the
    last line of ``out``. ``need_chip=False`` is for the rehearsal tests
    alone: the command line always looks for the chip."""
    out = out or sys.stdout
    err = err or sys.stderr
    cell = Cell(root, workload)
    info = require_chip(cell.chips) if need_chip else device_info()
    compiles = Compiles()
    tracer = Tracer(trace, os.path.join(root, ".bench_trace"),
                    cell.traffic.get("trace_s", 8.0))
    driver = cell.driver().Driver(cell, int(seed), float(seconds), tracer)

    driver.setup()
    driver.window()                # lead-in, [t_open, t_close], drain
    setup_s = driver.t_open - T_PROCESS_START
    peak = memory_peak_bytes(cell.chips)
    for name, nbytes in sorted(driver.program_memory().items()):
        print(f"memory program={name} argument_plus_temp_bytes={nbytes} "
              f"runtime_peak_bytes_in_use={peak}", file=err)
    driver.release()
    verdict = driver.check()       # the plain reference, after the window

    facts = driver.facts()
    if facts.get("stats1") is not None and tracer.stats_m1 is not None:
        # a traced run's counters end with its traced part: stopping the
        # profiler holds the loop while an open loop's arrivals go on, and
        # what follows is the catching up, not the cell
        facts["stats1"] = tracer.stats_m1
    facts["setup_s"] = setup_s
    facts["compile_s_in_setup"] = compiles.seconds_before(driver.t_open)
    facts["window_compiles"] = compiles.count_between(driver.t_open,
                                                      driver.t_close)
    device = dict(info, count=cell.chips, memory_peak_bytes=peak)
    result = {"correct": bool(verdict["correct"]),
              "attempted": int(facts["attempted"]),
              "failed": int(facts["failed"])}
    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            if m["name"] not in facts:
                raise KeyError(f"driver reported no {m['name']} for "
                               f"{workload}")
            metrics[m["name"]] = {"value": facts[m["name"]],
                                  "unit": m["unit"]}
    else:
        summary = tracer.summary(cell.chips)
        ctx = {"cell": cell, "config": cell.config, "traffic": cell.traffic,
               "peaks": cell.peaks(info["kind"]) if need_chip else None,
               "trace": summary, "facts": facts,
               "traced": (tracer.m0, tracer.m1)}
        for m in cell.per_layer:
            v = cell.reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            result["breakdown"] = {"device_ops": summary.top_ops(10),
                                   "idle_gaps": summary.top_gaps(10)}
    result["metrics"] = metrics
    result["device"] = device
    result["compared"] = verdict["compared"]
    print("compared " + fmt_compared(verdict["compared"]), file=err)
    print(json.dumps(result), file=out, flush=True)
    return result
