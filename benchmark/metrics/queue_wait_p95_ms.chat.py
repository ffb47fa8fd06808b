"""Scheduler: 95th percentile of due -> admitted (the program's own
``t_admitted`` stamp, which is on the harness's clock) over the requests due
in the window."""
from benchmark.readers import percentile


def read(ctx):
    waits = [(r.admitted - r.due) * 1e3 for r in ctx["facts"]["due"]
             if r.admitted is not None and r.due is not None]
    return percentile(waits, 95)
