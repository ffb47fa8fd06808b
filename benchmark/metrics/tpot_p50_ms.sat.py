"""Median time per output token of the requests that finished in the
TRACED part of the window (none of their tokens waited while the profiler
stopped, which blocks the stepping loop for 10-30 s); recorded beside the
saturated cell's rate, judges nothing."""
from benchmark.readers import in_traced, percentile


def read(ctx):
    tpot = [(r.token_t[-1] - r.token_t[0]) / (len(r.token_t) - 1) * 1e3
            for r in ctx["facts"]["requests"]
            if r.state == "ok" and len(r.token_t) > 1
            and r.done_t is not None and in_traced(ctx, r.done_t)]
    return percentile(tpot, 50)
