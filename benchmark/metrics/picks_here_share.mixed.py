"""Model step: picks that named an expert held on this chip over all picks
of the window (``serving.moe.picks_here`` over ``serving.moe.picks``), in
percent: 6.25 where routing is even and a sixteenth of the experts is
held."""
from benchmark.readers_granite import picks_share


def read(ctx):
    ps = picks_share(ctx)
    return None if ps is None else 100.0 * ps
