"""Device: idle share of the traced window, leaving out the gaps in which no
request was in the engine (the harness's ``idle_wait`` span)."""
from benchmark.readers import device_idle


def read(ctx):
    return device_idle(ctx, minus_span="idle_wait")
