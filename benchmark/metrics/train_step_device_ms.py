"""Training: device milliseconds of one step program, mean over the traced
part of the window. The wall time of a step less this is host dispatch."""
from benchmark.readers import TRAIN_PROGRAM, module_time


def read(ctx):
    t = module_time(ctx, TRAIN_PROGRAM)
    return None if t is None else 1e3 * t[0] / t[1]
