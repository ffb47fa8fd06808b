"""Model step: held experts that at least one row picked over held experts
offered, summed over the window's decode steps and expert layers (the
program's ``serving.moe.experts_hit`` over ``serving.moe.experts_held``), in
percent. At 32 rows and top-8 of 256 each held expert gets one row a step
on average: the stream kernel reads all 16 either way."""
from benchmark.readers_granite import counter_delta


def read(ctx):
    held = counter_delta(ctx, "serving.moe.experts_held")
    if held <= 0:
        return None
    return 100.0 * counter_delta(ctx, "serving.moe.experts_hit") / held
