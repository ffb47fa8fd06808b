"""Model step: device milliseconds of one decode step (one decode program
runs ``decode_chunk`` of them)."""
from benchmark.readers import DECODE_PROGRAM, module_time


def read(ctx):
    t = module_time(ctx, DECODE_PROGRAM)
    if t is None:
        return None
    k = int(ctx["config"]["serving"]["engine"]["decode_chunk"])
    return 1e3 * t[0] / (t[1] * k)
