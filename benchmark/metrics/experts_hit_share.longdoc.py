"""Model step: held experts that at least one row picked over held experts
offered, summed over the window's decode steps and layers (the program's
``serving.moe.experts_hit`` over ``serving.moe.experts_held``), in percent.
At 24 rows and top-4 of 128 about half the 32 held experts are hit a step:
the stream kernel reads them all either way, so its time is free of the
data."""
from benchmark.readers_granite import counter_delta


def read(ctx):
    held = counter_delta(ctx, "serving.moe.experts_held")
    if held <= 0:
        return None
    return 100.0 * counter_delta(ctx, "serving.moe.experts_hit") / held
