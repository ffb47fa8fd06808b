"""Model step: device milliseconds of one prefill-chunk program, mean over the
chunks in the traced part of the window (every chunk size: today's trace
gives them one name)."""
from benchmark.readers import PREFILL_PROGRAM, module_time


def read(ctx):
    t = module_time(ctx, PREFILL_PROGRAM)
    return None if t is None else 1e3 * t[0] / t[1]
