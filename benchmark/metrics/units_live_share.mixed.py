"""Model step: the (work unit, column block) grid steps of the grouped
expert GEMMs that owned a row, and so issued a matmul, over the grid steps
walked, summed over the window's prefill chunks and expert layers (the
program's ``serving.moe.units_live`` over ``serving.moe.units_walked``), in
percent."""
from benchmark.readers_granite import counter_delta


def read(ctx):
    walked = counter_delta(ctx, "serving.moe.units_walked")
    if walked <= 0:
        return None
    return 100.0 * counter_delta(ctx, "serving.moe.units_live") / walked
