"""Device: idle share of the traced window, 1 - busy / window."""
from benchmark.readers import device_idle


def read(ctx):
    return device_idle(ctx)
