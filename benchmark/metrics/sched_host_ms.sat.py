"""Scheduler: host milliseconds per ``step()`` outside the device phase, from
the program's ``serve.step.admit_ms`` and ``serve.step.host_overhead_ms``
histograms (count and total gained in the window, not their buckets)."""
from benchmark.readers import hist_delta


def read(ctx):
    f = ctx["facts"]
    admit = hist_delta(f, "serve.step.admit_ms")
    over = hist_delta(f, "serve.step.host_overhead_ms")
    steps = hist_delta(f, "serve.step.total_ms")
    if not admit or not over or not steps or steps[0] <= 0:
        return None
    return (admit[1] + over[1]) / steps[0]
