"""Scheduler: 95th percentile of due -> admitted (the program's own
``t_admitted`` stamp, which is on the harness's clock) over the requests
that were due in the TRACED part of the window and that the stepping loop
could see before it ended: stopping the profiler holds that loop for tens
of seconds, and a request due in the hole, or during the last step before
it, waits for the profiler and not for the scheduler."""
from benchmark.readers import percentile, seen_in_traced


def read(ctx):
    waits = [(r.admitted - r.due) * 1e3 for r in ctx["facts"]["due"]
             if r.admitted is not None and r.due is not None
             and seen_in_traced(ctx, r.due)]
    return percentile(waits, 95)
