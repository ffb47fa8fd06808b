"""Scheduler: host milliseconds per ``step()`` in which the device does not
have the step's work: the step's whole time less its run phase, from the
totals that ``serve.step.total_ms`` and ``serve.step.run_ms`` gained in the
window over the steps gained."""
from benchmark.readers import hist_delta


def read(ctx):
    f = ctx["facts"]
    total = hist_delta(f, "serve.step.total_ms")
    run = hist_delta(f, "serve.step.run_ms")
    if not total or not run or total[0] <= 0:
        return None
    return (total[1] - run[1]) / total[0]
