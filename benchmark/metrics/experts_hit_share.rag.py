"""Model step: held experts that at least one row picked over held experts
offered, summed over the window's decode steps and layers (the program's
``serving.moe.experts_hit`` over ``serving.moe.experts_held``), in percent.
Near 100 the expert stream's time cannot depend on the data."""
from benchmark.readers_granite import counter_delta


def read(ctx):
    held = counter_delta(ctx, "serving.moe.experts_held")
    if held <= 0:
        return None
    return 100.0 * counter_delta(ctx, "serving.moe.experts_hit") / held
